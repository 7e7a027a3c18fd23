"""Multi-device tests (subprocess with 8 forced host devices): sharding
rules, pipeline parallelism, flash-decoding combine, compressed psum,
cost-analysis calibration."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from jax.sharding import PartitionSpec as P

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_py(code: str, devices: int = 8, timeout: int = 360) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# sharding rules (no subprocess needed)
# ---------------------------------------------------------------------------

def test_rules_divisibility(smoke_graph):
    import jax
    from repro.distributed.sharding import make_rules, enforce_divisible
    from repro.configs import get_config
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)
    fm = FakeMesh()

    cfg = get_config("minitron-8b")     # kv=8 not divisible by 16
    rules = make_rules(cfg, fm)
    assert rules["tp_kv"] is None and rules["qheads"] == "model"
    cfg2 = get_config("qwen2-moe-a2.7b")  # kv=16 divisible
    rules2 = make_rules(cfg2, fm)
    assert rules2["tp_kv"] == "model"
    cfg3 = get_config("mamba2-1.3b")    # vocab 50280 not divisible
    assert make_rules(cfg3, fm)["vocab"] is None
    # enforce_divisible drops bad dims
    sp = enforce_divisible(P("model", "data"), (51865, 1024), fm)
    assert sp == P(None, "data")


def test_physical_specs_all_archs_divide():
    """Every resolved param sharding divides its dim on the 16×16 mesh."""
    from repro.distributed.sharding import physical_specs, _axis_size
    from repro.configs import get_config, list_archs
    from repro.models.api import build
    from repro.models.params import ParamDecl
    import jax

    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)
    fm = FakeMesh()
    for arch in [a for a in list_archs() if not a.startswith("graphsage")]:
        cfg = get_config(arch)
        model = build(cfg)
        specs = physical_specs(model.decls, cfg, fm)
        decls_flat = jax.tree.leaves(model.decls,
                                     is_leaf=lambda x: isinstance(x, ParamDecl))
        specs_flat = jax.tree.leaves(specs,
                                     is_leaf=lambda x: isinstance(x, P))
        for d, s in zip(decls_flat, specs_flat):
            for i, dim in enumerate(d.shape):
                ax = s[i] if i < len(s) else None
                assert dim % _axis_size(fm, ax) == 0, (arch, d.shape, s)


# ---------------------------------------------------------------------------
# subprocess multi-device
# ---------------------------------------------------------------------------

def test_cost_analysis_known_matmul():
    """Calibrate: per-device flops of a sharded matmul == 2MNK/devices."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        mesh = jax.make_mesh((8,), ("d",))
        M = N = K = 512
        f = jax.jit(lambda a, b: a @ b,
                    in_shardings=(NamedSharding(mesh, P("d", None)),
                                  NamedSharding(mesh, P(None, None))),
                    out_shardings=NamedSharding(mesh, P("d", None)))
        import numpy as np
        from repro.launch.xla_compat import cost_analysis_dict
        c = f.lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                    jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
        ca = cost_analysis_dict(c)
        assert ca, "backend produced no cost analysis"
        fl = ca["flops"]
        want = 2 * M * N * K / 8
        assert abs(fl - want) / want < 0.05, (fl, want)
        print("CALIBRATED", fl, want)
    """)
    assert "CALIBRATED" in out


def test_collective_parse_known_psum():
    """Collective-bytes parser sees the all-reduce of a known psum."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.dryrun import parse_collectives
        mesh = jax.make_mesh((8,), ("d",))
        f = jax.jit(lambda a: a.sum(axis=0),
                    in_shardings=NamedSharding(mesh, P("d", None)),
                    out_shardings=NamedSharding(mesh, P()))
        c = f.lower(jax.ShapeDtypeStruct((8, 1024), jnp.float32)).compile()
        per_op, total = parse_collectives(c.as_text())
        assert "all-reduce" in per_op, per_op
        # result is (1024,) f32 → 4096 B × factor 2
        assert per_op["all-reduce"]["bytes"] >= 8192, per_op
        print("PARSED", json.dumps(per_op))
        """.replace("import jax,", "import json, jax,"))
    assert "PARSED" in out


def test_flash_decode_shardmap_matches_ref():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.collectives import flash_decode_attention
        mesh = jax.make_mesh((8,), ("model",))
        B, T, H, Dh = 2, 64, 4, 32
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(0, 1, (B, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.normal(0, 1, (B, T, H, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (B, T, H, Dh)), jnp.float32)
        pos = jnp.asarray([17, 63], jnp.int32)
        fn = jax.jit(flash_decode_attention(mesh, "model"))
        o = fn(q, k, v, pos)
        # reference: full attention with causal-position mask
        s = jnp.einsum("bhe,bthe->bht", q, k)
        mask = jnp.arange(T)[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, -1)
        ref = jnp.einsum("bht,bthe->bhe", p, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
        print("FLASH_DECODE_OK")
    """)
    assert "FLASH_DECODE_OK" in out


def test_compressed_psum_shardmap():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.train.compression import compressed_psum_int8
        mesh = jax.make_mesh((8,), ("pod",))
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 128)),
                        jnp.float32)
        fn = jax.shard_map(lambda t: compressed_psum_int8(t, "pod"),
                           mesh=mesh, in_specs=P("pod", None),
                           out_specs=P("pod", None), check_vma=False)
        out = jax.jit(fn)(x)
        want = jnp.mean(x, axis=0)      # mean over the pod axis
        got = np.asarray(out)[0]
        err = np.abs(got - np.asarray(want)).max()
        scale = np.abs(np.asarray(x)).max() / 127
        assert err <= scale + 1e-5, (err, scale)
        print("CPSUM_OK", err)
    """)
    assert "CPSUM_OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pp import make_pipeline_fn, split_microbatches
        mesh = jax.make_mesh((4,), ("stage",))
        S, M, mb, D = 4, 8, 4, 16
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(0, 0.5, (S, D, D)), jnp.float32)

        def layer_fn(w, x):
            return jnp.tanh(x @ w)

        pipe = make_pipeline_fn(lambda p, x: layer_fn(p, x), S, M, mesh)
        x = jnp.asarray(rng.normal(0, 1, (M * mb, D)), jnp.float32)
        xs = split_microbatches(x, M)
        got = jax.jit(pipe)(Ws, xs).reshape(M * mb, D)
        ref = x
        for s in range(S):
            ref = layer_fn(Ws[s], ref)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        print("PP_OK")
    """)
    assert "PP_OK" in out


@pytest.mark.slow
def test_dryrun_cell_tiny_mesh():
    """run_cell machinery works end-to-end on a small forced-device mesh
    (uses the real 256/512-device path in launch/dryrun.py; here we only
    validate the single-cell JSON plumbing on 512 devices but the smallest
    arch/shape)."""
    out = run_py("""
        from repro.launch.dryrun import run_cell
        res = run_cell("whisper-medium", "decode_32k", "single")
        assert not res.get("skipped") and "error" not in res, res
        assert res["cost"]["flops_per_device"] > 0
        assert res["memory"]["peak_device_bytes"] > 0
        assert res["cost"]["collective_bytes_per_device"] >= 0
        print("CELL_OK")
    """, devices=512, timeout=900)
    assert "CELL_OK" in out


def test_peaks_keyed_by_device_kind():
    """The roofline peaks are looked up by ``device_kind``; a device kind
    with no table is an error, never the v5e numbers by default."""
    from repro.launch.mesh import peaks
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no peak table"):
        peaks("cpu")


def test_production_mesh_axes_are_auto():
    """Sharding constraints (distributed/sharding.py) need Auto axes;
    ``jax.make_mesh`` now defaults to Explicit ones."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    assert all(t == AxisType.Auto for t in mesh.axis_types)
