"""Version-compat helpers around XLA's AOT introspection APIs.

Side-effect free on import (unlike launch/dryrun.py, which forces 512 host
devices) — safe to import from tests and subprocesses that control their
own device count.
"""
from __future__ import annotations


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (``{}`` when the backend
    provides no analysis)."""
    ca = compiled.cost_analysis()
    return dict(ca) if ca else {}
