"""Host sampler: the share of the rejection path's proposals that became
picks, Σ``picks`` over Σ``proposals`` of ``a3gnn.sampler.hop`` in the
window, in % (``bench/spans.py``).  None where no row took that path, or
the program has no such span."""
from bench import spans


def read(ctx):
    rec = spans.entry(ctx, "sampler.hop")
    proposals = sum(a["proposals"] for a in rec["args"]) if rec else 0
    if not proposals:
        return None
    return 100 * sum(a["picks"] for a in rec["args"]) / proposals
