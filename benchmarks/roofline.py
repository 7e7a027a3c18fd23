"""Roofline analysis over the dry-run artifacts (deliverable g).

Per (arch × shape × mesh):
    compute    = HLO_FLOPs_per_device / peak_FLOP/s          [s]
    memory     = HLO_bytes_per_device / HBM_bw               [s]
    collective = collective_bytes_per_device / ICI_bw        [s]

plus the dominant term, MODEL_FLOPS = 6·N·D (train; 2·N_active·D per decoded
token), the useful-compute ratio MODEL_FLOPS / HLO_FLOPS, and the roofline
fraction = model-compute-time / max(term)s — the score we hillclimb in §Perf.

Usage:
    PYTHONPATH=src python -m benchmarks.roofline [--mesh single] [--tag opt]
    (also invoked by benchmarks.run)
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro.launch.mesh import peaks

ART = Path(__file__).resolve().parent / "artifacts"
DRY = ART / "dryrun"

# the dry-run projects the production mesh (launch/mesh.py), a TPU v5e pod
HW = peaks("TPU v5 lite")


# (seq_len, global_batch) per shape — tokens are recomputed here so stale
# artifacts with the old prefill token-count bug stay correct.
SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,            # one new token per sequence
    "long_500k": 1,
}


def model_flops_per_device(rec) -> float:
    """6·N·D for train (N active params); 2·N per processed token for
    prefill/decode."""
    n_active = rec["params_active"]
    toks = SHAPE_TOKENS.get(rec["shape"], rec["tokens_per_step"])
    factor = 6.0 if rec["kind"] == "train" else 2.0
    return factor * n_active * toks / rec["n_devices"]


def analyze_record(rec) -> dict:
    c = rec["cost"]
    t_compute = c["flops_per_device"] / HW["peak_flops_bf16"]
    t_memory = c["bytes_per_device"] / HW["hbm_bw"]
    t_coll = c["collective_bytes_per_device"] / HW["ici_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec)
    useful_ratio = mf / max(c["flops_per_device"], 1e-9)
    t_model = mf / HW["peak_flops_bf16"]
    bound = max(terms.values())
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec["kind"], "tag": rec.get("tag", ""),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops_per_device": mf,
        "useful_flops_ratio": useful_ratio,
        "roofline_fraction": t_model / max(bound, 1e-30),
        "peak_gib": rec["memory"]["peak_device_bytes"] / 2**30,
        "fits_hbm": rec["memory"]["peak_device_bytes"] < HW["hbm_bytes"],
        "step_lower_bound_s": bound,
    }


def load_records(mesh: str = "single", tag: str = ""):
    out = []
    d = DRY / mesh
    if not d.exists():
        return out
    for f in sorted(d.glob("*.json")):
        rec = json.loads(f.read_text())
        if rec.get("skipped") or "error" in rec:
            continue
        if rec.get("tag", "") != tag:
            continue
        out.append(rec)
    return out


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | dom | t_comp (ms) | t_mem (ms) | t_coll (ms) | "
           "useful | roofline | peak GiB | fits |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    body = ""
    for r in rows:
        body += (f"| {r['arch']} | {r['shape']} | {r['dominant'][:4]} | "
                 f"{r['t_compute_s']*1e3:.2f} | {r['t_memory_s']*1e3:.2f} | "
                 f"{r['t_collective_s']*1e3:.2f} | "
                 f"{r['useful_flops_ratio']:.2f} | "
                 f"{r['roofline_fraction']*100:.1f}% | "
                 f"{r['peak_gib']:.1f} | "
                 f"{'Y' if r['fits_hbm'] else 'N'} |\n")
    return hdr + body


def run(quick: bool = False, mesh: str = "single", tag: str = ""):
    from benchmarks.common import emit, save_json
    recs = load_records(mesh, tag)
    rows = [analyze_record(r) for r in recs]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    for r in rows:
        emit(f"roofline/{mesh}/{r['arch']}/{r['shape']}" +
             (f"/{tag}" if tag else ""),
             r["step_lower_bound_s"] * 1e6,
             f"dom={r['dominant']};roofline={r['roofline_fraction']*100:.1f}%;"
             f"useful={r['useful_flops_ratio']:.2f};peak_GiB={r['peak_gib']:.1f}")
    save_json(f"roofline_{mesh}" + (f"_{tag}" if tag else ""), rows)
    (ART / f"roofline_{mesh}{'_' + tag if tag else ''}.md").write_text(
        markdown_table(rows))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    rows = run(mesh=args.mesh, tag=args.tag)
    print(markdown_table(rows))


if __name__ == "__main__":
    main()
