"""Serving entry point — two engines behind one CLI.

LM token decode (continuous batching over prompts):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
      --requests 16 --batch 4 --max-new 12

Online GNN node inference over the training-side FeaturePlane (trains
briefly to warm params + cache, then serves node queries and applies a
streaming feature update mid-serving):

  PYTHONPATH=src python -m repro.launch.serve --gnn \
      --arch graphsage-products --smoke --queries 16 --batch 4

Partition-routed serving fabric (``--partitions`` > 1): a multi-partition
trainer warms per-partition planes, then a ``ServingFabric`` routes node
queries to owner-partition replicas behind SLO-aware admission, with a
mid-serving trainer → replica weight refresh:

  PYTHONPATH=src python -m repro.launch.serve --gnn \
      --arch graphsage-products --smoke --queries 32 --batch 4 \
      --partitions 2 --replicas 2 --slo-p99-ms 50
"""
from __future__ import annotations

import argparse

import numpy as np


def run_lm_serve(args):
    from repro.configs import get_config
    from repro.serve.engine import Engine, Request

    cfg = get_config(args.arch, smoke=args.smoke)
    eng = Engine(cfg, batch=args.batch, max_len=args.max_len,
                 temperature=args.temperature, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(2, args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(Request(rid=rid, prompt=prompt,
                           max_new_tokens=args.max_new))
    stats = eng.run_to_completion()
    print(f"[result] {stats['completed']} requests, {stats['tokens']} tokens "
          f"in {stats['seconds']:.2f}s → {stats['tokens_per_s']:.1f} tok/s; "
          f"TTFT p50 {stats['ttft_p50_ms']:.1f} ms "
          f"p99 {stats['ttft_p99_ms']:.1f} ms")
    return 0


def run_fabric_serve(args, cfg, graph):
    """Partition-routed fleet: warm a multi-partition trainer, serve the
    query load through a ``ServingFabric`` (ownership routing + replicas
    + SLO admission), refresh weights from the live trainer mid-serving,
    then drive a saturating burst to show explicit shedding."""
    from repro.core.multipart import MultiPartitionTrainer
    from repro.serve.fabric import ServingFabric
    from repro.serve.gnn_engine import GNNRequest

    cfg = cfg.replace(partitions=args.partitions)
    tr = MultiPartitionTrainer(graph, cfg, seed=args.seed)
    tr.run_epochs(1, max_steps_per_epoch=args.train_steps)
    print(f"[train] {args.train_steps} steps over {args.partitions} "
          f"partitions warmed the planes: "
          f"cache_hit_rate={tr.cache_hit_rate:.3f}")

    fab = ServingFabric.from_trainer(tr, batch=args.batch,
                                     replicas=args.replicas,
                                     slo_p99_ms=args.slo_p99_ms,
                                     seed=args.seed,
                                     timeout_ms=args.serve_timeout_ms)
    # trigger each replica's one jit compile BEFORE timing anything: a
    # ~250 ms compile inside the first served queries would poison the
    # SLO scheduler's service estimate into shedding the real load
    for part in fab.engines:
        for eng in part:
            owned = np.flatnonzero(eng.node_map >= 0)
            for j, v in enumerate(owned[:eng.batch]):
                eng.submit(GNNRequest(rid=-1 - j, node=int(v)))
            eng.run_to_completion()
    fab.window.reset()
    warm_per_part = fab.partition_completed()

    rng = np.random.default_rng(args.seed)
    nodes = rng.choice(np.where(graph.test_mask)[0], size=args.queries,
                       replace=False)
    for rid, v in enumerate(nodes):
        fab.submit(GNNRequest(rid=rid, node=int(v)))
    stats = fab.run_to_completion()
    per_part = [a - b for a, b in zip(fab.partition_completed(),
                                      warm_per_part)]
    print(f"[fabric] {stats['completed']} queries in "
          f"{stats['seconds']:.2f}s → {stats['queries_per_s']:.1f} q/s "
          f"across {args.partitions}×{args.replicas} replicas "
          f"(per-partition {per_part}); latency p50 "
          f"{stats['p50_ms']:.1f} ms p99 {stats['p99_ms']:.1f} ms")

    # trainer → replica hand-off: swap every replica's tree between steps
    tr.global_step()
    fab.refresh_weights()
    fab.submit(GNNRequest(rid=args.queries, node=int(nodes[0])))
    fab.run_to_completion()
    print(f"[refresh] trainer step → refresh_weights() → re-query "
          f"pred={fab.completed[-1].pred} (served on the updated tree)")

    # saturating burst: the door sheds what it cannot serve inside the SLO
    burst = np.where(fab.plan.owner_of(
        np.arange(graph.num_nodes)) >= 0)[0][:args.queries * 8]
    mark = fab.slo.offered
    for rid, v in enumerate(burst):
        fab.submit(GNNRequest(rid=10_000 + rid, node=int(v)))
    fab.run_to_completion()
    offered = fab.slo.offered - mark
    print(f"[slo] burst of {offered} offered at target "
          f"{fab.slo.slo_p99_ms:.0f} ms: shed {fab.slo.shed} "
          f"(fraction {fab.shed_fraction:.2f}), deferrals "
          f"{fab.slo.deferrals} — degradation is explicit, not queued")
    return 0


def run_gnn_serve(args):
    """Online GNN inference: brief training warms the params AND the γ/Θ
    feature cache, then the SAME FeaturePlane instance serves node
    queries — shared hit/miss accounting proves the reuse — and a
    mid-serving ``FeatureStore.update_rows`` is reflected in the very
    next prediction."""
    from repro.configs import get_config
    from repro.core.a3gnn import A3GNNTrainer
    from repro.graph.storage import FeatureStore
    from repro.graph.synthetic import dataset_like
    from repro.serve.gnn_engine import GNNInferenceEngine, GNNRequest

    cfg = get_config(args.arch, smoke=args.smoke)
    if getattr(cfg, "family", None) != "gnn":
        raise SystemExit(f"--gnn serving needs a GNN arch "
                         f"(e.g. graphsage-products); {args.arch!r} is a "
                         f"{getattr(cfg, 'family', 'non-GNN')} config — "
                         f"drop --gnn for token-decode serving")
    if args.sampling_device:
        cfg = cfg.replace(sampling_device=args.sampling_device)
    graph = dataset_like(cfg, seed=args.seed)
    print(f"[data] {graph.name}: {graph.num_nodes} nodes, "
          f"{graph.num_edges} edges, {graph.num_classes} classes")

    if args.partitions > 1:
        return run_fabric_serve(args, cfg, graph)

    tr = A3GNNTrainer(graph, cfg, seed=args.seed)
    pipe = tr.make_pipeline()
    try:
        pipe.run(max_steps=args.train_steps)
    finally:
        pipe.shutdown()               # workers down; the plane stays live
    hits_trained = tr.cache.stats.hits if tr.cache else 0
    print(f"[train] {args.train_steps} steps warmed the cache: "
          f"{hits_trained} hits, "
          f"hit_rate={tr.cache_hit_rate:.3f}")

    eng = GNNInferenceEngine.from_trainer(tr, batch=args.batch,
                                          plane=pipe.plane, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    test_ids = np.where(graph.test_mask)[0]
    nodes = rng.choice(test_ids, size=args.queries, replace=True)
    for rid, v in enumerate(nodes):
        eng.submit(GNNRequest(rid=rid, node=int(v)))
    stats = eng.run_to_completion()
    print(f"[serve] {stats['completed']} queries in {stats['seconds']:.2f}s "
          f"→ {stats['queries_per_s']:.1f} q/s over "
          f"{stats['engine_steps']} engine steps "
          f"(batch={args.batch}, backend={eng.plane.backend}); "
          f"latency p50 {stats['p50_ms']:.1f} ms p99 {stats['p99_ms']:.1f} ms")
    if tr.cache is not None:
        print(f"[plane] shared with training: hits {hits_trained} → "
              f"{tr.cache.stats.hits} (serving added "
              f"{tr.cache.stats.hits - hits_trained}), "
              f"hit_rate={tr.cache.stats.hit_rate:.3f}")

    # streaming update mid-serving: the store fans the row out through the
    # plane (cache-resident copy + device-mirror invalidation), so the
    # re-query sees the drifted feature immediately
    store = FeatureStore(graph)
    eng.plane.subscribe_to(store)
    node = int(nodes[0])
    before = eng.completed[0].pred
    store.update_rows(np.array([node]),
                      np.full((1, graph.feat_dim), 1.0, np.float32))
    eng.submit(GNNRequest(rid=args.queries, node=node))
    eng.run_to_completion()
    after = eng.completed[-1].pred
    print(f"[stream] update_rows(node {node}) → store v{store.version}; "
          f"re-query pred {before} → {after} "
          f"(drift observed through the live plane)")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # LM decode knobs
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    # GNN serving knobs
    ap.add_argument("--gnn", action="store_true",
                    help="serve online GNN node predictions through the "
                         "training-side FeaturePlane (serve/gnn_engine.py); "
                         "implied when --arch names a GNN config "
                         "(graphsage-*)")
    ap.add_argument("--queries", type=int, default=16,
                    help="node-prediction requests to serve (--gnn)")
    ap.add_argument("--train-steps", type=int, default=4,
                    help="brief training steps to warm params + cache "
                         "before serving (--gnn)")
    ap.add_argument("--sampling-device", default=None,
                    choices=[None, "cpu", "device", "auto"],
                    help="feature-plane backend for the serving gather")
    ap.add_argument("--partitions", type=int, default=1,
                    help="> 1 serves through the partition-routed "
                         "ServingFabric (serve/fabric.py) instead of one "
                         "engine (--gnn)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas per partition behind the "
                         "fabric's shared admission scheduler")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="target p99 for SLO-aware admission (0 disables "
                         "shedding; fabric only)")
    ap.add_argument("--serve-timeout-ms", type=float, default=0.0,
                    help="per-request fabric timeout before retry-on-"
                         "another-replica (≤ 0 disables — the pre-seam "
                         "behavior; fabric only)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.gnn or args.arch.startswith("graphsage"):
        return run_gnn_serve(args)
    return run_lm_serve(args)


if __name__ == "__main__":
    raise SystemExit(main())
