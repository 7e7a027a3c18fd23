"""Pallas TPU kernels for the compute hot-spots A³GNN optimizes.

Each subpackage: ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd wrapper: Pallas kernel or jnp oracle — ``platform.py`` compiles
the kernel for a TPU and interprets it elsewhere), ``ref.py`` (pure-jnp
oracle).

  reservoir/        vectorized weighted-reservoir top-m neighbor selection
  gather/           device-map feature-cache row gather
  segment_agg/      masked neighbor mean aggregation (GraphSAGE SpMM analogue)
  fused_gather_agg/ gather + layer-0 neighbor mean in one pass (no
                    materialized batch feature tensor on the hit path)
  flash_attention/  blockwise fused attention fwd (LM stack hot-spot)
"""
