"""Training entry point.

Two workloads behind one CLI:

  * GNN (the paper):  --arch graphsage-products [--baseline pyg_like] ...
    runs A³GNN end-to-end on a synthetic twin dataset with the configured
    sampling/caching/parallelism strategy, reporting the paper's metrics.

  * LM (assigned archs): --arch minitron-8b --smoke ... runs the reduced
    config on the host mesh with the real train step, host data pipeline,
    checkpointing and fault-tolerance supervisor.  On a real TPU slice the
    same code path takes the production mesh (launch/mesh.py).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch graphsage-products --steps 30
  PYTHONPATH=src python -m repro.launch.train --arch graphsage-products \
      --smoke --autotune --episodes-autotune 4
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke --steps 10
"""
from __future__ import annotations

import argparse
import time


def run_gnn_multipartition(args, cfg, graph):
    """Scale-out GNN path: locality-partitioned data parallelism under the
    fault-tolerance supervisor, with a restart-path restore proof."""
    from repro.core.a3gnn import make_trainer
    from repro.train.checkpoint import CheckpointManager

    tr = make_trainer(graph, cfg, seed=args.seed)
    plan = tr.plan
    print(f"[partition] {plan.parts} partitions ({plan.method}): "
          f"sizes={[len(ns) for ns in plan.node_sets]} "
          f"edge_locality={plan.edge_locality(graph):.3f} "
          f"halo={plan.halo_counts}")
    if plan.halo_budget > 0:
        print(f"[halo] budget={plan.halo_budget}/partition "
              f"kept={[len(hs) for hs in plan.halo_sets]} "
              f"kept_information={plan.kept_information(graph):.3f} "
              f"(vs {plan.edge_locality(graph):.3f} at budget=0) "
              f"exchange={tr.halo_exchange_bytes/2**10:.1f} KiB")
    # fresh dir per run unless the caller pins one — a reused dir would
    # let keep-k GC favor a previous (longer) run's higher step numbers
    # and the restore proof below would resurrect stale parameters
    import tempfile
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(
        prefix=f"ckpt_gnn_p{cfg.partitions}_")
    rep = tr.fit_supervised(args.steps, ckpt_dir,
                            ckpt_every=max(args.steps // 2, 1))
    acc = tr.evaluate()
    halo_note = (f" halo_hit={tr.halo_hit_rate:.3f}"
                 if plan.halo_budget > 0 else "")
    print(f"[result] {rep.steps_run} global steps "
          f"({rep.steps_run * plan.parts} partition mini-batches), "
          f"checkpoints={rep.checkpoints} acc={acc:.4f} "
          f"cache_hit={tr.cache_hit_rate:.3f}{halo_note}")
    # restart-path proof: rebuild a fresh trainer and restore the committed
    # checkpoint (the same machinery the autotune `partitions` knob uses)
    tr2 = make_trainer(graph, cfg, seed=args.seed)
    step = tr2.restore(CheckpointManager(ckpt_dir, async_save=False))
    print(f"[restore] fresh trainer restored from step {step} "
          f"(global_steps={tr2.global_steps}) acc={tr2.evaluate():.4f}")
    return 0


def run_gnn(args):
    from repro.configs import get_config
    from repro.graph.synthetic import dataset_like
    from repro.core.a3gnn import A3GNNTrainer, apply_baseline

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.mode:
        cfg = cfg.replace(parallel_mode=args.mode)
    if args.bias_rate is not None:
        cfg = cfg.replace(bias_rate=args.bias_rate)
    if args.partitions is not None:
        cfg = cfg.replace(partitions=args.partitions)
    if args.halo_budget is not None:
        cfg = cfg.replace(halo_budget=args.halo_budget)
    if args.halo_refresh_interval is not None:
        cfg = cfg.replace(halo_refresh_interval=args.halo_refresh_interval)
    if args.rebalance_drift is not None:
        cfg = cfg.replace(rebalance_drift=args.rebalance_drift)
    if args.sampling_device is not None:
        cfg = cfg.replace(sampling_device=args.sampling_device)
    if args.fused_gather_agg:
        cfg = cfg.replace(fused_gather_agg=True)
    cfg = apply_baseline(cfg, args.baseline)
    graph = dataset_like(cfg, seed=args.seed)
    print(f"[data] {graph.name}: {graph.num_nodes} nodes, "
          f"{graph.num_edges} edges")
    if cfg.partitions > 1:
        return run_gnn_multipartition(args, cfg, graph)
    tr = A3GNNTrainer(graph, cfg, seed=args.seed)
    if args.autotune:
        acfg = cfg.autotune.replace(episodes=args.episodes_autotune,
                                    steps_per_episode=args.steps,
                                    seed=args.seed)
        rep = tr.fit_autotuned(acfg)
        for ep in rep.episodes:
            c, m = ep.config, ep.metrics
            print(f"[episode {ep.index}] γ={c['bias_rate']:.2f} "
                  f"Θ={c['cache_volume_mb']:.2f}MB "
                  f"mode={c['parallel_mode']} workers={int(c['workers'])} | "
                  f"thr={m['throughput']:.2f} steps/s "
                  f"mem={m['memory']/2**20:.1f} MiB acc={m['accuracy']:.3f} "
                  f"hit={ep.cache_hit_rate:.2f}")
        b, m = rep.best, rep.best.metrics
        print(f"[autotune] best=episode {b.index} "
              f"thr={m['throughput']:.2f} steps/s "
              f"(baseline {rep.baseline_metrics['throughput']:.2f}) "
              f"changed={sorted(rep.changed_knobs())}")
        print(f"[pareto] {len(rep.pareto_points())} non-dominated "
              f"measured points")
        return 0
    res = tr.run_epochs(args.epochs, max_steps_per_epoch=args.steps)
    print(f"[result] thr={res.throughput_epochs_s:.4f} ep/s "
          f"({res.throughput_steps_s:.2f} steps/s) "
          f"mem={res.memory_bytes/2**20:.1f} MiB "
          f"acc={res.test_acc:.4f} hit_rate={res.cache_hit_rate:.3f}")
    st = res.stats.stage_times()
    print(f"[stages] sample={st.t_sample*1e3:.1f}ms "
          f"batch={st.t_batch*1e3:.1f}ms train={st.t_train*1e3:.1f}ms")
    return 0


def run_lm(args):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.api import build
    from repro.models.params import init_params
    from repro.train.trainer import make_train_step
    from repro.train.optimizer import get_optimizer
    from repro.train.data import SyntheticTokens, PrefetchLoader
    from repro.train.checkpoint import CheckpointManager
    from repro.train.fault_tolerance import TrainSupervisor

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build(cfg)
    opt = get_optimizer(cfg)
    step_fn, _ = make_train_step(model, cfg, opt)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    rng = jax.random.PRNGKey(args.seed)
    params = init_params(model.decls, rng,
                         dtype_override=jnp.dtype(cfg.param_dtype))
    opt_state = opt.init(params)
    data = SyntheticTokens(cfg.vocab_size, args.batch, args.seq,
                           seed=args.seed, n_batches=args.steps)
    loader = PrefetchLoader(data, workers=args.workers)
    ckpt = CheckpointManager(args.ckpt_dir or f"/tmp/ckpt_{args.arch}",
                             keep=2, async_save=True)

    state = {"params": params, "opt_state": opt_state}
    it = iter(loader)

    def one_step(state, step):
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, metrics = jstep(state["params"], state["opt_state"], batch)
        if step % max(args.steps // 10, 1) == 0:
            print(f"  step {step}: loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
        return {"params": p, "opt_state": o}

    sup = TrainSupervisor(ckpt, ckpt_every=max(args.steps // 3, 1))
    t0 = time.time()
    state, rep = sup.run(state, one_step, args.steps)
    dt = time.time() - t0
    toks = args.steps * args.batch * args.seq
    print(f"[result] {args.steps} steps in {dt:.1f}s "
          f"({toks/dt:.0f} tok/s), checkpoints={rep.checkpoints}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # GNN knobs
    ap.add_argument("--baseline", default=None,
                    choices=[None, "a3gnn", "pyg_like", "quiver_like"])
    ap.add_argument("--mode", default=None,
                    choices=[None, "seq", "mode1", "mode2"])
    ap.add_argument("--bias-rate", type=float, default=None)
    ap.add_argument("--partitions", type=int, default=None,
                    help="data-parallel graph partitions (scale-out path; "
                         "host-simulated mesh when devices < partitions)")
    ap.add_argument("--halo-budget", type=int, default=None,
                    help="per-partition cap on boundary feature rows "
                         "exchanged through the mesh (0 = drop cut edges, "
                         "the paper's no-remote-access setting)")
    ap.add_argument("--halo-refresh-interval", type=int, default=None,
                    help="re-run the bounded halo exchange every N global "
                         "steps when streamed feature updates left halo "
                         "copies stale (0 = explicit refresh only)")
    ap.add_argument("--rebalance-drift", type=float, default=None,
                    help="cut-fraction drift past the plan baseline that "
                         "triggers an incremental partition re-balance "
                         "between global steps on a mutating graph "
                         "(boundary-node migration; <= 0 disables)")
    ap.add_argument("--sampling-device", default=None,
                    choices=[None, "cpu", "device", "auto"],
                    help="feature-plane backend for batch generation: "
                         "cpu (numpy cache), device (Pallas cache gather), "
                         "auto (probe jax.devices())")
    ap.add_argument("--fused-gather-agg", action="store_true",
                    help="all-hop fused device pipeline: batch generation "
                         "defers feature work to the train step, which "
                         "resolves the input hop from encoded cache slots "
                         "+ a miss sideband and aggregates every hop in "
                         "place (one jit signature per model/level_caps; "
                         "all model families, bit-exact with unfused)")
    ap.add_argument("--autotune", action="store_true",
                    help="run the online auto-tuning controller (§III-C)")
    ap.add_argument("--episodes-autotune", type=int, default=4)
    # LM knobs
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.arch.startswith("graphsage"):
        return run_gnn(args)
    return run_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
