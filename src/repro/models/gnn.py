"""GNN models over sampled blocks: GraphSAGE (mean), GCN, GAT, GIN.

Blocks use fixed-fanout padded neighbor matrices (core/sampling.py) so every
hop is a dense masked gather + matmul — the TPU-native formulation of the
CSR SpMM the GPU frameworks use (kernels/segment_agg provides the Pallas
path).  Variable node counts are bucketed to powers of two (graph/batch.py)
so jit recompiles only a handful of times.

Every layer has two expressions of the same math:

- **unfused** (default): materialize the gathered-neighbor tensor
  (``_gather_neighbors``) and reduce it — simple, and the historical
  reference the fused path is tested against.
- **fused** (``fused=True``): the hop's aggregation runs through
  ``kernels/segment_agg.neighbor_agg`` consuming the previous layer's
  output buffer in place — the (Nd, fanout, D) tensor never
  materializes.  Layer 0 goes further: ``gnn_forward_allfused`` resolves
  input rows straight out of the feature-plane cache table via
  ``kernels/fused_gather_agg`` (encoded slots + miss sideband), so the
  (pad_src0, F) input-feature tensor never materializes either.

The train steps always run the fused kernels with ``use_pallas=False``:
the jitted pure-jnp oracle is differentiable, and the Pallas path is
forward-only today.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_gather_agg.ops import gather_aggregate
from repro.kernels.fused_gather_agg.ref import resolve_rows_ref as resolve_rows
from repro.kernels.segment_agg.ops import neighbor_agg
from repro.models.params import decl


def layer_dims(cfg) -> List[Tuple[int, int]]:
    dims = [cfg.feat_dim] + [cfg.hidden] * (cfg.num_layers - 1) + [cfg.num_classes]
    return list(zip(dims[:-1], dims[1:]))


def decls_gnn(cfg):
    layers = []
    for (din, dout) in layer_dims(cfg):
        if cfg.model == "graphsage":
            layers.append({"w_self": decl((din, dout), (None, None)),
                           "w_neigh": decl((din, dout), (None, None)),
                           "b": decl((dout,), (None,), init="zeros")})
        elif cfg.model == "gcn":
            layers.append({"w": decl((din, dout), (None, None)),
                           "b": decl((dout,), (None,), init="zeros")})
        elif cfg.model == "gat":
            layers.append({"w": decl((din, dout), (None, None)),
                           "a_src": decl((dout,), (None,), scale=0.1, init="normal"),
                           "a_dst": decl((dout,), (None,), scale=0.1, init="normal"),
                           "b": decl((dout,), (None,), init="zeros")})
        elif cfg.model == "gin":
            layers.append({"eps": decl((1,), (None,), init="zeros"),
                           "w1": decl((din, dout), (None, None)),
                           "b1": decl((dout,), (None,), init="zeros"),
                           "w2": decl((dout, dout), (None, None)),
                           "b2": decl((dout,), (None,), init="zeros")})
        else:
            raise ValueError(cfg.model)
    return {"layers": layers}


def _gather_neighbors(h_src, neigh_idx):
    """h_src (Ns,D), neigh_idx (Nd,F) with -1 pad → (nb (Nd,F,D), mask)."""
    mask = (neigh_idx >= 0)
    idx = jnp.maximum(neigh_idx, 0)
    nb = h_src[idx]
    return nb * mask[..., None].astype(h_src.dtype), mask


def _mean_agg(h_src, neigh_idx):
    nb, mask = _gather_neighbors(h_src, neigh_idx)
    cnt = jnp.maximum(mask.sum(-1, keepdims=True), 1).astype(h_src.dtype)
    return nb.sum(1) / cnt


def _sum_agg(h_src, neigh_idx):
    nb, _ = _gather_neighbors(h_src, neigh_idx)
    return nb.sum(1)


# ---------------------------------------------------------------------------
# combine stages: what each model does AFTER the neighbor aggregation.
# Shared between the unfused layers, the fused layers, and the all-fused
# layer-0 entry (which gets (h_dst, agg) from kernels/fused_gather_agg).
# ---------------------------------------------------------------------------

def _sage_combine(p, h_dst, agg_mean, neigh_idx, act):
    out = h_dst @ p["w_self"] + agg_mean @ p["w_neigh"] + p["b"]
    return jax.nn.relu(out) if act else out


def _gcn_combine(p, h_dst, agg_mean, neigh_idx, act):
    # sampled-mean approximation of sym-normalized aggregation incl. self-loop
    mask = (neigh_idx >= 0)
    cnt = jnp.maximum(mask.sum(-1, keepdims=True), 1).astype(h_dst.dtype)
    z = (agg_mean * cnt + h_dst) / (cnt + 1.0)
    out = z @ p["w"] + p["b"]
    return jax.nn.relu(out) if act else out


def _gin_combine(p, h_dst, agg_sum, neigh_idx, act):
    z = (1.0 + p["eps"]) * h_dst + agg_sum
    out = jax.nn.relu(z @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
    return jax.nn.relu(out) if act else out


# model → (combine fn, aggregation mode its layer consumes)
_COMBINE = {"graphsage": (_sage_combine, "mean"),
            "gcn": (_gcn_combine, "mean"),
            "gin": (_gin_combine, "sum")}


def _agg(h_src, neigh_idx, mode, *, fused, use_pallas):
    if fused:
        return neighbor_agg(neigh_idx, h_src, mode=mode,
                            use_pallas=use_pallas)
    return _mean_agg(h_src, neigh_idx) if mode == "mean" \
        else _sum_agg(h_src, neigh_idx)


def sage_layer(p, h_src, neigh_idx, *, act=True, fused=False,
               use_pallas=False):
    h_dst = h_src[:neigh_idx.shape[0]]
    agg = _agg(h_src, neigh_idx, "mean", fused=fused,
               use_pallas=use_pallas)
    return _sage_combine(p, h_dst, agg, neigh_idx, act)


def gcn_layer(p, h_src, neigh_idx, *, act=True, fused=False,
              use_pallas=False):
    h_dst = h_src[:neigh_idx.shape[0]]
    agg = _agg(h_src, neigh_idx, "mean", fused=fused,
               use_pallas=use_pallas)
    return _gcn_combine(p, h_dst, agg, neigh_idx, act)


def gin_layer(p, h_src, neigh_idx, *, act=True, fused=False,
              use_pallas=False):
    h_dst = h_src[:neigh_idx.shape[0]]
    agg = _agg(h_src, neigh_idx, "sum", fused=fused,
               use_pallas=use_pallas)
    return _gin_combine(p, h_dst, agg, neigh_idx, act)


def gat_layer(p, h_src, neigh_idx, *, act=True, fused=False,
              use_pallas=False):
    n_dst = neigh_idx.shape[0]
    z_src = h_src @ p["w"]                               # (Ns,D')
    z_dst = z_src[:n_dst]
    if fused:
        # attention scores need only the scalar projections z@a_src —
        # gather those (Nd, fanout) scalars, not (Nd, fanout, D') rows
        mask = (neigh_idx >= 0)
        s_src = z_src @ p["a_src"]                       # (Ns,)
        e = jax.nn.leaky_relu(
            jnp.where(mask, s_src[jnp.maximum(neigh_idx, 0)], 0.0)
            + (z_dst @ p["a_dst"])[:, None],
            negative_slope=0.2)
        e = jnp.where(mask, e, -1e30)
        e_self = jax.nn.leaky_relu(z_dst @ (p["a_src"] + p["a_dst"]))[:, None]
        alla = jax.nn.softmax(jnp.concatenate([e, e_self], axis=1), axis=1)
        agg = neighbor_agg(neigh_idx, z_src, mode="sum",
                           weights=alla[:, :-1],
                           use_pallas=use_pallas) + alla[:, -1:] * z_dst
    else:
        nb, mask = _gather_neighbors(z_src, neigh_idx)   # (Nd,F,D')
        e = jax.nn.leaky_relu(nb @ p["a_src"] + (z_dst @ p["a_dst"])[:, None],
                              negative_slope=0.2)
        e = jnp.where(mask, e, -1e30)
        # include self edge in the softmax
        e_self = jax.nn.leaky_relu(z_dst @ (p["a_src"] + p["a_dst"]))[:, None]
        alla = jax.nn.softmax(jnp.concatenate([e, e_self], axis=1), axis=1)
        agg = jnp.einsum("nf,nfd->nd", alla[:, :-1], nb) + alla[:, -1:] * z_dst
    out = agg + p["b"]
    return jax.nn.elu(out) if act else out


_LAYER_FNS = {"graphsage": sage_layer, "gcn": gcn_layer, "gat": gat_layer,
              "gin": gin_layer}


def gnn_forward(params, features, neigh_idxs: List[jnp.ndarray], cfg, *,
                fused=False, use_pallas=False):
    """features (pad_src0, F); neigh_idxs[i] (pad_dst_i, fanout_i) with the
    chained-padding invariant pad_dst_i == pad_src_{i+1}."""
    fn = _LAYER_FNS[cfg.model]
    h = features.astype(jnp.dtype(cfg.compute_dtype))
    n = len(params["layers"])
    for i, (p, idx) in enumerate(zip(params["layers"], neigh_idxs)):
        h = fn(p, h, idx, act=(i < n - 1), fused=fused,
               use_pallas=use_pallas)
    return h                                              # (pad_seeds, classes)


def gnn_forward_allfused(params, enc0, aux0, table, neigh_idxs, cfg, *,
                         use_pallas=False):
    """All-hop fused forward: layer-0 inputs are the encoded slots ``enc0``
    resolved against the feature-plane cache ``table`` and the host miss
    sideband ``aux0`` (kernels/fused_gather_agg) — the (pad_src0, F)
    input-feature tensor never materializes — and every hop ≥ 1 runs the
    fused per-hop aggregation over the previous layer's output buffer."""
    dt = jnp.dtype(cfg.compute_dtype)
    n = len(params["layers"])
    p0, idx0 = params["layers"][0], neigh_idxs[0]
    kw = dict(fused=True, use_pallas=use_pallas)
    if cfg.model == "gat":
        # attention needs the per-src projection: resolve the rows (still no
        # neighbor tensor) and run the fused GAT layer on them
        rows = resolve_rows(enc0, table, aux0).astype(dt)
        h = gat_layer(p0, rows, idx0, act=(n > 1), **kw)
    else:
        combine, mode = _COMBINE[cfg.model]
        h_dst, agg = gather_aggregate(enc0, idx0, table, aux0, mode=mode,
                                      use_pallas=use_pallas)
        h = combine(p0, h_dst.astype(dt), agg.astype(dt), idx0, act=(n > 1))
    fn = _LAYER_FNS[cfg.model]
    for i, (p, idx) in enumerate(zip(params["layers"][1:], neigh_idxs[1:]),
                                 start=1):
        h = fn(p, h, idx, act=(i < n - 1), **kw)
    return h


def _softmax_ce(logits, labels):
    logits = logits[:labels.shape[0]].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(lse - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc


def gnn_loss(params, features, neigh_idxs, labels, cfg):
    logits = gnn_forward(params, features, neigh_idxs, cfg)
    return _softmax_ce(logits, labels)


def gnn_loss_allfused(params, enc0, aux0, table, neigh_idxs, labels, cfg):
    logits = gnn_forward_allfused(params, enc0, aux0, table, neigh_idxs, cfg)
    return _softmax_ce(logits, labels)


def make_train_step(cfg, opt):
    """jit-able (params, opt_state, features, neigh_idxs, labels) step."""

    @jax.jit
    def step(params, opt_state, features, neigh_idxs, labels):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gnn_loss(p, features, neigh_idxs, labels, cfg),
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params, cfg.lr)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
        return params, opt_state, loss, acc

    return step


def make_train_step_allfused(cfg, opt):
    """All-hop fused twin of ``make_train_step``: consumes
    (enc0, aux0, table) from the feature plane instead of the materialized
    feature tensor.  With level-capped buffers (graph/batch.py
    ``compute_level_caps``) every batch hits ONE jit signature —
    ``step.counters['traces']`` counts retraces (incremented inside the jit
    body, so it bumps once per compilation) and ``['calls']`` counts
    invocations; tests assert traces == 1."""
    counters = {"traces": 0, "calls": 0}

    @jax.jit
    def _step(params, opt_state, enc0, aux0, table, neigh_idxs, labels):
        counters["traces"] += 1
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gnn_loss_allfused(p, enc0, aux0, table, neigh_idxs,
                                        labels, cfg),
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params, cfg.lr)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params,
                              updates)
        return params, opt_state, loss, acc

    def step(params, opt_state, enc0, aux0, table, neigh_idxs, labels):
        counters["calls"] += 1
        return _step(params, opt_state, enc0, aux0, table, neigh_idxs, labels)

    step.counters = counters
    return step


def make_grad_fn(cfg):
    """jit-able gradient step WITHOUT the optimizer update — the
    multi-partition path (core/multipart.py) averages gradients across
    partitions before applying a single shared update."""

    @jax.jit
    def gfn(params, features, neigh_idxs, labels):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gnn_loss(p, features, neigh_idxs, labels, cfg),
            has_aux=True)(params)
        return grads, loss, acc

    return gfn


def make_grad_fn_allfused(cfg):
    """All-hop fused twin of ``make_grad_fn`` (multi-partition path)."""
    counters = {"traces": 0, "calls": 0}

    @jax.jit
    def _gfn(params, enc0, aux0, table, neigh_idxs, labels):
        counters["traces"] += 1
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gnn_loss_allfused(p, enc0, aux0, table, neigh_idxs,
                                        labels, cfg),
            has_aux=True)(params)
        return grads, loss, acc

    def gfn(params, enc0, aux0, table, neigh_idxs, labels):
        counters["calls"] += 1
        return _gfn(params, enc0, aux0, table, neigh_idxs, labels)

    gfn.counters = counters
    return gfn


def make_apply_fn(cfg, opt):
    """jit-able optimizer application for pre-averaged gradients."""

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params, cfg.lr)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params,
                              updates)
        return params, opt_state

    return apply


def make_eval_fn(cfg):
    @jax.jit
    def ev(params, features, neigh_idxs, labels):
        logits = gnn_forward(params, features, neigh_idxs, cfg)
        logits = logits[:labels.shape[0]]
        return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return ev
