"""Chip smoke test: drive the A³GNN trainer and GNN serving once on a TPU.

    python chip_smoke.py              # one chip: train, fetch check, serve
    python chip_smoke.py --chips 4    # four chips: multi-partition training
    python chip_smoke.py --smoke      # the small config (CPU rehearsals)

One chip runs ``graphsage-products`` at its published width (F=100,
hidden 256, 3 layers, fanout (15,10,5), batch 512, the 98k-node twin,
Θ=40 MB) through the normal entry points: the trainer built as
``launch/train.py`` builds it with the device feature plane, so the Pallas
cache gather runs compiled, then node queries served through
``GNNInferenceEngine.from_trainer`` on the same plane, as
``launch/serve.py`` does.  ``--chips 4`` runs only the multi-partition
phase: ``MultiPartitionTrainer`` over four partitions with a halo budget
on the real device mesh.  Weights are random, made from ``--seed``.

Every phase checks its outputs against a reference.  The last line of
standard output is ``{"ok": true, "device": {...}}``; it is printed only
when every check passed on a TPU.  Anything else exits non-zero.  One
process does everything: no child process touches JAX.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


STEPS = 5          # training steps (global steps with --chips 4)
QUERIES = 16       # node queries served
BATCH = 4          # serving engine slots


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise CheckFailed(what)
    print(f"[check] {what}: ok", flush=True)


def _import_repo():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        raise CheckFailed(f"no repro package under {src}: run chip_smoke.py "
                          f"from a checkout of the repository")
    sys.path.insert(0, str(src))


def train_and_serve(args) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.a3gnn import A3GNNTrainer
    from repro.core.cache import FeatureCache
    from repro.core.feature_plane import (GATHER_CHUNK_ROWS,
                                          DeviceFeaturePlane,
                                          HostFeaturePlane)
    from repro.graph.synthetic import dataset_like
    from repro.kernels.gather.ops import cache_gather
    from repro.serve.gnn_engine import GNNInferenceEngine, GNNRequest

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = get_config("graphsage-products", smoke=args.smoke).replace(
        sampling_device="device")
    t0 = time.perf_counter()
    graph = dataset_like(cfg, seed=args.seed)
    print(f"[data] {graph.name}: {graph.num_nodes} nodes, {graph.num_edges} "
          f"edges, F={graph.feat_dim}; built in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    t0 = time.perf_counter()
    tr = A3GNNTrainer(graph, cfg, seed=args.seed)
    pipe = tr.make_pipeline()
    plane = pipe.plane
    check(isinstance(plane, DeviceFeaturePlane), "device feature plane")
    check(plane.use_pallas == on_tpu, f"Pallas gather selected on "
          f"{plane.device.platform} (use_pallas={plane.use_pallas})")
    print(f"[setup] trainer + plane in {time.perf_counter() - t0:.1f} s: "
          f"cache {tr.cache.capacity} rows of {graph.num_nodes}", flush=True)

    # the gather the plane dispatches, lowered for the plane's device
    slots = np.zeros(GATHER_CHUNK_ROWS, np.int32)
    plane._ensure_synced()
    hlo = cache_gather.lower(slots, plane._dev_table,
                             use_pallas=plane.use_pallas).as_text()
    compiled = "tpu_custom_call" in hlo
    print(f"[gather] cache_gather lowered for {plane.device.platform}: "
          f"tpu_custom_call={compiled}", flush=True)
    check(compiled == on_tpu, "cache gather runs as a compiled kernel "
          "exactly when the device is a TPU")

    try:
        losses = []
        for step in range(STEPS):
            t0 = time.perf_counter()
            stats = pipe.run(max_steps=1)
            losses += stats.losses
            check(stats.reissued == 0, f"step {step}: no batch re-issued")
            print(f"[train] step {step}: loss={stats.losses[-1]:.6f} "
                  f"acc={stats.accs[-1]:.4f} "
                  f"({time.perf_counter() - t0:.1f} s incl. compile)",
                  flush=True)
    finally:
        pipe.shutdown()               # workers down; the plane stays live
    check(len(losses) == STEPS and bool(np.isfinite(losses).all()),
          f"{STEPS} finite losses")

    # rows the device plane fetches == the host feature store, for cache
    # hits on the trainer's plane and for hits and misses on a plane whose
    # cache holds half the graph
    rng = np.random.default_rng(args.seed)
    ids = rng.choice(graph.num_nodes, size=min(2048, graph.num_nodes),
                     replace=False)
    half_mb = graph.num_nodes / 2 * graph.feat_dim * 4 / 2**20
    half = DeviceFeaturePlane(graph, FeatureCache(graph, half_mb))
    hit = half.cache.is_cached(ids)
    check(bool(hit.any() and (~hit).any()), f"sample has {int(hit.sum())} "
          f"hits and {int((~hit).sum())} misses on the half cache")
    for name, p in (("trainer plane", plane), ("half-cache plane", half)):
        got = p.fetch(ids)
        check(np.array_equal(got, graph.features[ids]),
              f"{name} fetch equals graph.features exactly")

    # serving on the trainer's plane vs. the host plane, same params
    nodes = rng.choice(np.where(graph.test_mask)[0], size=QUERIES,
                       replace=False)
    preds = {}
    for name, p in (("device", plane), ("host", HostFeaturePlane(graph))):
        eng = GNNInferenceEngine.from_trainer(tr, batch=BATCH, plane=p,
                                              seed=args.seed)
        for rid, v in enumerate(nodes):
            eng.submit(GNNRequest(rid=rid, node=int(v)))
        t0 = time.perf_counter()
        st = eng.run_to_completion()
        preds[name] = {r.rid: r.pred for r in eng.completed}
        print(f"[serve] {name} plane: {st['completed']} queries in "
              f"{st['engine_steps']} engine steps "
              f"({time.perf_counter() - t0:.1f} s incl. compile)", flush=True)
    check(len(preds["device"]) == QUERIES,
          f"{QUERIES} queries served")
    check(preds["device"] == preds["host"],
          "device-plane predictions equal host-plane predictions")


def multipartition(args) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core.multipart import MultiPartitionTrainer
    from repro.distributed.collectives import halo_all_to_all
    from repro.graph.synthetic import dataset_like

    parts = 4
    cfg = get_config("graphsage-products", smoke=args.smoke).replace(
        partitions=parts, halo_budget=32 if args.smoke else 1024,
        sampling_device="device")
    graph = dataset_like(cfg, seed=args.seed)
    t0 = time.perf_counter()
    tr = MultiPartitionTrainer(graph, cfg, seed=args.seed)
    plan = tr.plan
    print(f"[partition] {parts} partitions: sizes "
          f"{[len(ns) for ns in plan.node_sets]}, halo kept "
          f"{[len(hs) for hs in plan.halo_sets]}; set up in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    check(isinstance(tr.mesh, Mesh) and len(set(tr.devices)) == parts,
          f"real {parts}-device mesh")

    # the all-reduce the trainer runs, with its inputs and output kept
    seen = []
    allreduce = tr._allreduce

    def spy(trees):
        mean = allreduce(trees)
        seen.append((trees, mean))
        return mean

    tr._allreduce = spy
    try:
        for step in range(STEPS):
            t0 = time.perf_counter()
            tr.global_step()
            losses = [s.pipe.stats.losses[-1] for s in tr.slots]
            print(f"[train] global step {step}: partition losses "
                  f"{[f'{x:.6f}' for x in losses]} "
                  f"({time.perf_counter() - t0:.1f} s incl. compile)",
                  flush=True)
    finally:
        for s in tr.slots:
            s.pipe.shutdown()
    losses = [x for s in tr.slots for x in s.pipe.stats.losses]
    check(bool(np.isfinite(losses).all()), f"{len(losses)} finite losses")
    check(all(s.pipe.stats.reissued == 0 for s in tr.slots),
          "no batch re-issued")

    trees, mean = seen[-1]
    grad_devices = [next(iter(jax.tree.leaves(t)[0].devices()))
                    for t in trees]
    print(f"[grads] partition gradients on {grad_devices}", flush=True)
    check(len(set(grad_devices)) == parts,
          f"gradients live on {parts} distinct devices")
    host = [jax.tree.map(np.asarray, t) for t in trees]
    want = jax.tree.map(lambda *xs: np.mean(np.stack(xs), axis=0), *host)
    for got, ref in zip(jax.tree.leaves(mean), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5,
                                   atol=1e-7)
    check(True, "grad_allreduce mean equals the numpy mean")

    owned = [sub.features[:len(ns)] for sub, ns in
             zip(plan.subgraphs, plan.node_sets)]
    halo, volume = halo_all_to_all(tr.mesh)(plan, owned)
    check(all(np.array_equal(rows, graph.features[hs])
              for rows, hs in zip(halo, plan.halo_sets)),
          f"halo_all_to_all rows equal graph.features[halo_set] "
          f"({volume} bytes)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-partition phase")
    ap.add_argument("--smoke", action="store_true",
                    help="small config (for CPU rehearsals)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        _import_repo()
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        import jaxlib
        try:
            from importlib.metadata import version
            libtpu = version("libtpu")
        except Exception:  # noqa: BLE001 — report only
            libtpu = "not installed"
        devs = jax.devices()
        dev = devs[0]
        print(f"[env] jax {jax.__version__} jaxlib {jaxlib.__version__} "
              f"libtpu {libtpu}; {len(devs)} × {dev.platform} "
              f"({dev.device_kind}); compile cache {cache_dir}", flush=True)
        check(len(devs) >= args.chips,
              f"{args.chips} device(s) present ({len(devs)} found)")
        if not args.smoke:
            # the full-size phase is for the chip; --smoke rehearses the
            # same checks at a small size anywhere
            check(dev.platform == "tpu", f"platform is tpu ({dev.platform})")
        t0 = time.perf_counter()
        (multipartition if args.chips == 4 else train_and_serve)(args)
        print(f"[done] phase took {time.perf_counter() - t0:.1f} s",
              flush=True)
        check(dev.platform == "tpu", f"platform is tpu ({dev.platform})")
    except CheckFailed as e:
        print(f"[FAIL] {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
