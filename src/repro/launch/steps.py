"""Per-cell step programs: every assigned (arch × shape) cell as a
jit-loweable function with abstract inputs + production shardings.

``build_cell(arch_id, shape_name, mesh, multi_pod)`` returns a ``Cell``
holding the step function, ShapeDtypeStruct arguments, input shardings and
the analytic MODEL_FLOPS for the roofline ratio. ``launch/dryrun`` lowers and
compiles each cell; nothing here allocates device memory.

Step kinds per family:
- LM train:     loss + grad + AdamW update          (train_step)
- LM prefill:   prompt -> last logits + KV caches   (prefill_step)
- LM decode:    one new token against a KV cache    (serve_step)
- GNN:          full-graph / sampled-minibatch / batched-molecule train_step
- recsys:       CTR train / online & bulk serve / 1M-candidate retrieval
- paper engine: the IFE query engine at full published graph scale
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import base as cfgbase
from ..core.dispatcher import build_engine, pad_sources, _axes_size
from ..core.policies import POLICIES
from ..core.extend import GraphOperands
from ..graph.csr import SCAN_CHUNK, BinnedEll
from ..graph.partition import padded_n
from ..models import dcn_v2 as dcn
from ..models import transformer as tfm
from ..models.gnn import equiformer_v2 as eqv2_m
from ..models.gnn import mace as mace_m
from ..models.gnn import pna as pna_m
from ..models.gnn import schnet as schnet_m
from ..nn.module import (
    set_activation_rules,
    sharding_rules,
    shardings_from_axes,
    split_boxed,
)
from ..optim.adamw import AdamWConfig, AdamWState, adamw_init, adamw_update

GNN_MODULES = {
    "mace": mace_m,
    "equiformer-v2": eqv2_m,
    "pna": pna_m,
    "schnet": schnet_m,
}


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str
    fn: Callable  # ready to jit (or already jitted for paper engine)
    args: tuple  # ShapeDtypeStruct pytrees
    in_shardings: Any  # tuple matching args, or None (shard_map programs)
    model_flops: float  # analytic useful FLOPs per step execution
    iters_scale: float = 1.0  # roofline multiplier for dynamic while bodies
    notes: str = ""
    prejitted: bool = False  # fn is already jax.jit-wrapped (paper engine)
    donate: tuple = ()  # donated arg indices (in-place update semantics)
    out_shardings: Any = None


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def _all_axes(multi_pod: bool):
    return ("pod", "data", "model") if multi_pod else ("data", "model")


def _ns(mesh, *spec_parts):
    return NamedSharding(mesh, P(*spec_parts))


def _sanitize(params, shardings, mesh):
    """jit(in_shardings=...) requires dims divisible by their mesh axes
    (unlike with_sharding_constraint, which pads). Drop the spec on any
    param dim that does not divide — e.g. dcn-v2's 429-wide cross kernels
    or PNA's 75-wide towers stay replicated on that dim."""

    def fix(leaf, sh):
        spec = list(sh.spec) + [None] * (leaf.ndim - len(sh.spec))
        out = []
        for dim, part in zip(leaf.shape, spec):
            axes = (part,) if isinstance(part, str) else (part or ())
            size = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
                if axes else 1
            out.append(part if size > 1 and dim % size == 0 else None)
        return NamedSharding(mesh, P(*out))

    return jax.tree.map(fix, params, shardings)


# =========================================================================
# LM family
# =========================================================================

# microbatch counts tuned against measured single-shot activation temps
_N_MICRO = {
    "deepseek-coder-33b": 4,
    "olmoe-1b-7b": 4,
    "llama4-maverick-400b-a17b": 8,
}

def _lm_abstract_params(cfg, mesh, rules):
    boxed = jax.eval_shape(lambda: tfm.init(jax.random.PRNGKey(0), cfg))
    params, axes = split_boxed(boxed)
    shard = _sanitize(params, shardings_from_axes(axes, mesh, rules), mesh)
    return params, shard


def _lm_attn_flops(cfg, B, S, causal=True, cache_w=None):
    """Attention matmul FLOPs (QK^T + PV), fwd only, all layers.

    cache_w: decode mode — per-token attention against a W-deep cache."""
    total = 0.0
    for i in range(cfg.n_layers):
        kind = cfg.layer_kind(i)
        if cache_w is not None:
            w_eff = min(cfg.window, cache_w) if kind in ("local", "chunk") \
                else cache_w
            total += 4.0 * B * w_eff * cfg.n_heads * cfg.d_head
        else:
            s_eff = min(cfg.window, S) if kind in ("local", "chunk") else S
            # causal ~ half the S x s_eff rectangle
            total += 4.0 * B * S * s_eff * cfg.n_heads * cfg.d_head * (
                0.5 if causal else 1.0
            )
    return total


def _lm_cell(spec, shape, mesh, multi_pod) -> Cell:
    cfg = spec.full_config()
    if shape.kind == "train":
        # launcher policy (not part of the published arch configs):
        # "minimal" named remat saves the two d_model-wide sublayer outputs
        # per layer; for deep/wide models even those stacks exceed HBM, so
        # fall back to carry-only ("full") remat — ~33% extra fwd compute
        # for O(L·d) saved bytes
        dims_ = shape.dims
        dp = 16  # data-axis width (both meshes)
        n_micro = _N_MICRO.get(spec.arch_id, 1)
        saved = (3 * cfg.n_layers * (dims_["global_batch"] // dp // n_micro)
                 * (dims_["seq_len"] // 16) * cfg.d_model * 2)
        cfg = dataclasses.replace(
            cfg, remat="full" if saved > 6e9 else "minimal"
        )
    # train/prefill: sequence-parallel residual stream (scan carries saved
    # for backward shrink by the TP degree); decode: TP activations.
    rules = sharding_rules(
        multi_pod, seq_parallel=shape.kind in ("train", "prefill")
    )
    set_activation_rules(rules)
    params, pshard = _lm_abstract_params(cfg, mesh, rules)
    ba = _batch_axes(multi_pod)
    dims = shape.dims
    B, S = dims["global_batch"], dims["seq_len"]
    N = cfg.active_params()

    if shape.kind == "train":
        # llama4-maverick's 400B total params need bf16 moments to fit
        moment_dtype = (
            jnp.bfloat16 if cfg.total_params() > 1e11 else jnp.float32
        )
        ocfg = AdamWConfig(lr=3e-4, moment_dtype=moment_dtype)
        opt = jax.eval_shape(lambda p: adamw_init(p, ocfg), params)
        opt_shard = AdamWState(
            step=_ns(mesh), mu=pshard, nu=pshard
        )
        batch = {
            "tokens": sds((B, S), jnp.int32),
            "labels": sds((B, S), jnp.int32),
        }
        bshard = {k: _ns(mesh, ba, None) for k in batch}

        # microbatch gradient accumulation: activation + MoE-dispatch temps
        # scale with per-device tokens; n_micro is tuned per arch from the
        # measured single-shot footprints (EXPERIMENTS.md §Dry-run). The
        # gradient buffer (param-sharded f32) is the only extra state.
        def train_step(params, opt, batch):
            if n_micro == 1:
                loss, grads = jax.value_and_grad(tfm.loss_fn)(
                    params, cfg, batch
                )
            else:
                mb = jax.tree.map(
                    lambda a: a.reshape(n_micro, B // n_micro, *a.shape[1:]),
                    batch,
                )

                def micro(acc, b):
                    l, g = jax.value_and_grad(tfm.loss_fn)(params, cfg, b)
                    return jax.tree.map(jnp.add, acc, g), l

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                grads, losses = jax.lax.scan(micro, zeros, mb)
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = losses.mean()
            new_p, new_o, gnorm = adamw_update(grads, opt, params, ocfg)
            return new_p, new_o, loss, gnorm

        flops = 6.0 * N * (B * S) + 3.0 * _lm_attn_flops(cfg, B, S)
        return Cell(
            spec.arch_id, shape.name, "train", train_step,
            (params, opt, batch), (pshard, opt_shard, bshard), flops,
            notes=f"6ND={6.0 * N * B * S:.3e} n_micro={n_micro}",
            donate=(0, 1),
        )

    if shape.kind == "prefill":
        tokens = sds((B, S), jnp.int32)

        def prefill_step(params, tokens):
            return tfm.prefill(params, cfg, tokens, max_seq=S)

        flops = 2.0 * N * (B * S) + _lm_attn_flops(cfg, B, S)
        return Cell(
            spec.arch_id, shape.name, "prefill", prefill_step,
            (params, tokens), (pshard, _ns(mesh, ba, None)), flops,
        )

    # decode: one new token against a seq_len-deep KV cache
    assert shape.kind == "decode", shape.kind
    caches = jax.eval_shape(
        lambda: tfm.init_model_cache(cfg, B, S, jnp.bfloat16)
    )
    # KV-cache sharding: batch over data axes when it divides; the cache
    # sequence dim is sharded over "model" (decode_32k) or over ALL axes
    # (long_500k, batch=1) — flash-decoding-style distributed attention.
    data_sz = _axes_size(mesh, ba)
    if B >= data_sz and B % data_sz == 0:
        seq_axes = ("model",)
        cache_batch = ba
    else:
        seq_axes = ba + ("model",)
        cache_batch = None

    def _cache_spec(leaf):
        if leaf.ndim == 5:  # k/v: [groups, B, W, KV, hd]
            return _ns(mesh, None, cache_batch, seq_axes, None, None)
        assert leaf.ndim == 2  # slot_pos: [groups, W]
        return _ns(mesh, None, seq_axes)

    cache_shard = jax.tree.map(_cache_spec, caches)
    tokens = sds((B, 1), jnp.int32)
    pos = sds((), jnp.int32)

    def serve_step(params, caches, tokens, pos):
        return tfm.decode(params, cfg, caches, tokens, pos)

    flops = 2.0 * N * B + _lm_attn_flops(cfg, B, None, cache_w=S)
    return Cell(
        spec.arch_id, shape.name, "decode", serve_step,
        (params, caches, tokens, pos),
        (pshard, cache_shard, _ns(mesh, cache_batch, None), _ns(mesh)),
        flops,
        notes=f"KV cache W={S}, seq sharded over {seq_axes}",
        donate=(1,),
    )


def lm_components(arch_id: str, shape_name: str, mesh: Mesh,
                  multi_pod: bool) -> list:
    """Compositional roofline probes for LM cells.

    XLA's HLO cost analysis counts a while/scan body ONCE regardless of trip
    count, so the monolithic cell under-reports everything inside the
    layer-scan and the CE-chunk scan. Each component here is a standalone
    program with a STATIC trip multiplier (Cell.iters_scale); summing
    trips x terms reconstructs the true per-step cost:

      train:   n_groups x layer_group(fwd+bwd) + (S/ce_chunk) x ce_chunk
               + 1 x optimizer update (+ embedding, folded into ce/opt)
      prefill: n_groups x layer_group(fwd)     + 1 x unembed(last position)
      decode:  n_groups x decode_group         + 1 x unembed(one token)
    """
    spec = cfgbase.get(arch_id)
    shape = {s.name: s for s in spec.shapes}[shape_name]
    cfg = spec.full_config()
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat="minimal")
    rules = sharding_rules(
        multi_pod, seq_parallel=shape.kind in ("train", "prefill")
    )
    set_activation_rules(rules)
    params, pshard = _lm_abstract_params(cfg, mesh, rules)
    ba = _batch_axes(multi_pod)
    B, S = shape.dims["global_batch"], shape.dims["seq_len"]
    G = cfg.n_groups

    # one group's params: drop the leading stack dim
    gparams = jax.tree.map(
        lambda l: sds(l.shape[1:], l.dtype), params["blocks"]
    )
    gshard = jax.tree.map(
        lambda l, sh: NamedSharding(mesh, P(*sh.spec[1:])),
        params["blocks"], pshard["blocks"],
    )
    unemb_key = "embed" if cfg.tie_embeddings else "unembed"
    emb = params[unemb_key]["table"]
    emb_sh = pshard[unemb_key]["table"]
    res_sharding = _ns(
        mesh, ba, "model" if shape.kind in ("train", "prefill") else None,
        None,
    )
    comps = []

    if shape.kind in ("train", "prefill"):
        x = sds((B, S, cfg.d_model), cfg.dtype)
        pos = sds((B, S), jnp.int32)

        def group_fwd(gp, x, positions):
            for j in range(cfg.group_size):
                x, _ = tfm._layer_apply(gp[f"layer_{j}"], cfg, j, x,
                                        positions)
            return x

        if shape.kind == "train":
            body = tfm._remat(cfg, group_fwd)

            def group_fwd_bwd(gp, x, positions):
                y, vjp = jax.vjp(lambda g, xx: body(g, xx, positions), gp, x)
                dg, dx = vjp(jnp.ones_like(y))
                return dg, dx

            comps.append(Cell(
                arch_id, shape_name, "comp", group_fwd_bwd,
                (gparams, x, pos),
                (gshard, res_sharding, _ns(mesh, ba, None)),
                0.0, iters_scale=float(G), notes="layer_group fwd+bwd",
                out_shardings=(gshard, res_sharding),
            ))

            C = min(cfg.ce_chunk, S)
            xc = sds((B, C, cfg.d_model), cfg.dtype)
            yc = sds((B, C), jnp.int32)

            def ce_chunk(table, x_c, y_c):
                p = {"embed": {"table": table}}

                def nll(table_, x_):
                    logits = tfm._unembed(
                        {unemb_key: {"table": table_}}, cfg, x_
                    )
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    return -jnp.take_along_axis(
                        logp, y_c[..., None], axis=-1
                    ).sum()

                loss, vjp = jax.vjp(nll, table, x_c)
                return vjp(jnp.float32(1.0))

            comps.append(Cell(
                arch_id, shape_name, "comp", ce_chunk,
                (emb, xc, yc),
                (emb_sh, res_sharding, _ns(mesh, ba, None)),
                0.0, iters_scale=float(S // C), notes="ce_chunk fwd+bwd",
                out_shardings=(emb_sh, res_sharding),
            ))

            moment_dtype = (
                jnp.bfloat16 if cfg.total_params() > 1e11 else jnp.float32
            )
            ocfg = AdamWConfig(lr=3e-4, moment_dtype=moment_dtype)
            opt = jax.eval_shape(lambda p: adamw_init(p, ocfg), params)
            opt_shard = AdamWState(step=_ns(mesh), mu=pshard, nu=pshard)

            def opt_update(grads, opt, params):
                return adamw_update(grads, opt, params, ocfg)[:2]

            comps.append(Cell(
                arch_id, shape_name, "comp", opt_update,
                (params, opt, params), (pshard, opt_shard, pshard),
                0.0, iters_scale=1.0, notes="optimizer update",
                donate=(1, 2),
            ))
        else:  # prefill: fwd only + per-group kv materialization
            def group_prefill(gp, x, positions):
                caches = {}
                for j in range(cfg.group_size):
                    key = f"layer_{j}"
                    s = cfg.attn_settings(cfg.layer_kind(j))
                    from ..nn.attention import prefill_kv

                    xin = tfm._norm(cfg, gp[key]["ln_attn"], x)
                    caches[key] = prefill_kv(gp[key]["attn"], s, xin,
                                             positions, S)
                    x, _ = tfm._layer_apply(gp[key], cfg, j, x, positions)
                return x, caches

            comps.append(Cell(
                arch_id, shape_name, "comp", group_prefill,
                (gparams, x, pos),
                (gshard, res_sharding, _ns(mesh, ba, None)),
                0.0, iters_scale=float(G), notes="layer_group prefill",
            ))

            xe = sds((B, 1, cfg.d_model), cfg.dtype)

            def unembed_last(table, x_):
                return tfm._unembed({unemb_key: {"table": table}}, cfg, x_)

            comps.append(Cell(
                arch_id, shape_name, "comp", unembed_last,
                (emb, xe), (emb_sh, _ns(mesh, ba, None, None)),
                0.0, iters_scale=1.0, notes="unembed last",
            ))
        return comps

    # decode
    assert shape.kind == "decode"
    caches = jax.eval_shape(
        lambda: tfm.init_model_cache(cfg, B, S, jnp.bfloat16)
    )
    gcache = jax.tree.map(lambda a: sds(a.shape[1:], a.dtype), caches)
    data_sz = _axes_size(mesh, ba)
    if B >= data_sz and B % data_sz == 0:
        seq_axes, cache_batch = ("model",), ba
    else:
        seq_axes, cache_batch = ba + ("model",), None

    def _cspec(leaf):
        if leaf.ndim == 4:
            return _ns(mesh, cache_batch, seq_axes, None, None)
        return _ns(mesh, seq_axes)

    gcache_sh = jax.tree.map(_cspec, gcache)
    x = sds((B, 1, cfg.d_model), cfg.dtype)
    pos = sds((), jnp.int32)

    def decode_group(gp, gc, x, pos):
        new = {}
        for j in range(cfg.group_size):
            key = f"layer_{j}"
            x, c = tfm._layer_decode(gp[key], cfg, j, x, gc[key], pos)
            new[key] = c
        return x, new

    comps.append(Cell(
        arch_id, shape_name, "comp", decode_group,
        (gparams, gcache, x, pos),
        (gshard, gcache_sh, _ns(mesh, cache_batch, None, None), _ns(mesh)),
        0.0, iters_scale=float(G), notes="decode group", donate=(1,),
    ))

    def unembed_tok(table, x_):
        return tfm._unembed({unemb_key: {"table": table}}, cfg, x_)

    comps.append(Cell(
        arch_id, shape_name, "comp", unembed_tok,
        (emb, x), (emb_sh, _ns(mesh, cache_batch, None, None)),
        0.0, iters_scale=1.0, notes="unembed token",
    ))
    return comps


# =========================================================================
# GNN family
# =========================================================================

def _round_up(x, m):
    return -(-x // m) * m


def _gnn_batch_specs(arch_id, cfg, n, e, d_feat, mesh, multi_pod):
    """Abstract GNN batch + shardings. Node arrays shard over the batch
    (fsdp) axes; edge arrays are DESTINATION-ALIGNED SLABS (one slab per
    node shard, see models/gnn/common.set_edge_slabs) sharded over all axes
    — slab dim over the node shards, slab interiors over "model"."""
    from ..models.gnn import common as gnn_common

    aa = _all_axes(multi_pod)
    ba = _batch_axes(multi_pod)
    n_dev = _axes_size(mesh, aa)
    k_slabs = _axes_size(mesh, ba)
    gnn_common.set_edge_slabs(k_slabs)
    e_pad = _round_up(e, n_dev * k_slabs // math.gcd(n_dev, k_slabs))
    n_pad = _round_up(n, k_slabs)
    batch = {
        "edge_src": sds((e_pad,), jnp.int32),
        "edge_dst": sds((e_pad,), jnp.int32),
    }
    shard = {
        "edge_src": _ns(mesh, aa),
        "edge_dst": _ns(mesh, aa),
    }
    geometric = arch_id != "pna"
    if geometric:
        batch["positions"] = sds((n_pad, 3), jnp.float32)
        batch["species"] = sds((n_pad,), jnp.int32)
        shard["positions"] = _ns(mesh, ba, None)
        shard["species"] = _ns(mesh, ba)
    if d_feat:
        batch["node_feat"] = sds((n_pad, d_feat), jnp.float32)
        shard["node_feat"] = _ns(mesh, ba, None)
    return batch, shard, n_pad, e_pad


def _gnn_flops(arch_id, cfg, n, e):
    """Analytic useful FLOPs for one fwd pass (documented approximations;
    2 FLOPs per MAC). GNN message passing is gather/scatter-bound, so these
    count only the dense contractions."""
    d = cfg.d_hidden
    if arch_id == "pna":
        # per layer: 12 aggregated features of width d -> d (tower MLP) on
        # nodes + per-edge message transform d->d
        per = 2.0 * e * d * d + 2.0 * n * (12 * d) * d
        return cfg.n_layers * per + 2.0 * n * cfg.d_feat * d
    if arch_id == "schnet":
        # interaction: edge filter (n_rbf->d->d) + node d->d mixes
        per = 2.0 * e * (cfg.n_rbf * d + d * d) + 3 * 2.0 * n * d * d
        return cfg.n_interactions * per
    if arch_id == "mace":
        lm = (cfg.l_max + 1) ** 2
        # A-basis: edges contract rbf·Y·h (d·lm each); product basis:
        # correlation-order Gaunt contractions on nodes (lm^2·d per order)
        per = 2.0 * e * d * lm * (cfg.n_rbf + lm) + (
            2.0 * n * d * lm * lm * cfg.correlation_order
        ) + 2.0 * n * d * d * lm
        return cfg.n_layers * per
    if arch_id == "equiformer-v2":
        lm = (cfg.l_max + 1) ** 2
        m_width = 2 * cfg.m_max + 1
        # eSCN SO(2) conv per edge: O(lm * m_width * d^2) after alignment,
        # + attention scores/values per edge
        per = 2.0 * e * (lm * m_width * d * d / max(cfg.l_max, 1) + 2 * d * d)
        per += 2.0 * n * d * d * 4  # node FFN
        return cfg.n_layers * per
    raise ValueError(arch_id)


def _gnn_cell(spec, shape, mesh, multi_pod) -> Cell:
    module = GNN_MODULES[spec.arch_id]
    cfg = spec.full_config()
    rules = sharding_rules(multi_pod)
    set_activation_rules(rules)
    dims = shape.dims
    ba = _batch_axes(multi_pod)

    if shape.kind == "full_graph":
        n, e, d_feat = dims["n_nodes"], dims["n_edges"], dims["d_feat"]
        if spec.arch_id == "pna":
            n_out = 47 if shape.name == "ogb_products" else 40
            cfg = dataclasses.replace(cfg, d_feat=d_feat, n_out=n_out)
        else:
            # geometric archs read species+positions; raw features are
            # additionally projected in via d_feat
            cfg = dataclasses.replace(cfg, d_feat=d_feat, n_out=8)
        batch, bshard, n_pad, e_pad = _gnn_batch_specs(
            spec.arch_id, cfg, n, e, cfg.d_feat, mesh, multi_pod
        )
        batch["targets"] = sds((n_pad, cfg.n_out), jnp.float32)
        bshard["targets"] = _ns(mesh, ba, None)
        seeds = None
        n_eff, e_eff = n, e
    elif shape.kind == "minibatch":
        bn = dims["batch_nodes"]
        f1, f2 = dims["fanout"]
        n_pad0 = bn * (1 + f1 + f1 * f2)  # 1024·166 sampled tree nodes
        e = bn * (f1 + f1 * f2)
        cfg = dataclasses.replace(cfg, n_out=8) if spec.arch_id != "pna" \
            else dataclasses.replace(cfg, d_feat=100, n_out=47)
        batch, bshard, n_pad, e_pad = _gnn_batch_specs(
            spec.arch_id, cfg, n_pad0, e, cfg.d_feat, mesh, multi_pod
        )
        batch["targets"] = sds((bn, cfg.n_out), jnp.float32)
        bshard["targets"] = _ns(mesh, ba, None)
        seeds = bn
        n_eff, e_eff = n_pad0, e
    else:  # molecule: disjoint union of 128 small graphs
        assert shape.kind == "batched"
        bsz, npg, epg = dims["batch"], dims["n_nodes"], dims["n_edges"]
        n, e = bsz * npg, bsz * epg
        cfg = dataclasses.replace(cfg, n_out=1) if spec.arch_id != "pna" \
            else dataclasses.replace(cfg, d_feat=16, n_out=1)
        batch, bshard, n_pad, e_pad = _gnn_batch_specs(
            spec.arch_id, cfg, n, e, cfg.d_feat, mesh, multi_pod
        )
        batch["graph_ids"] = sds((n_pad,), jnp.int32)
        bshard["graph_ids"] = _ns(mesh, ba)
        batch["targets"] = sds((bsz,), jnp.float32)
        bshard["targets"] = _ns(mesh, ba)
        seeds = ("graph", bsz)
        n_eff, e_eff = n, e

    boxed = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), cfg))
    params, axes = split_boxed(boxed)
    pshard = _sanitize(params, shardings_from_axes(axes, mesh, rules), mesh)
    ocfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
    opt = jax.eval_shape(lambda p: adamw_init(p, ocfg), params)
    opt_shard = AdamWState(step=_ns(mesh), mu=pshard, nu=pshard)
    n_graphs = seeds[1] if isinstance(seeds, tuple) else None

    def loss_fn(p, batch):
        b = dict(batch)
        targets = b.pop("targets")
        if n_graphs is not None:
            b["n_graphs"] = n_graphs
        out = module.apply(p, cfg, b)
        if n_graphs is not None:
            pred = out["graph_out"][:, 0]
        elif isinstance(seeds, int):
            pred = out["node_out"][:seeds]
        else:
            pred = out["node_out"]
        return jnp.mean(jnp.square(pred - targets))

    def train_step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_p, new_o, gnorm = adamw_update(grads, opt, params, ocfg)
        return new_p, new_o, loss, gnorm

    flops = 3.0 * _gnn_flops(spec.arch_id, cfg, n_eff, e_eff)  # fwd+bwd
    return Cell(
        spec.arch_id, shape.name, shape.kind, train_step,
        (params, opt, batch), (pshard, opt_shard, bshard), flops,
        notes=f"n={n_eff} e={e_eff}",
        donate=(0, 1),
    )


# =========================================================================
# recsys (dcn-v2)
# =========================================================================

def _dcn_flops(cfg, B, fwd_only=False):
    d0 = cfg.x0_dim
    f = 2.0 * B * d0 * d0 * cfg.n_cross_layers
    d_in = d0
    for d_out in cfg.mlp:
        f += 2.0 * B * d_in * d_out
        d_in = d_out
    f += 2.0 * B * d_in  # head
    # embedding gather ~ bytes not flops; count the segment adds
    f += B * cfg.n_sparse * cfg.embed_dim
    return f if fwd_only else 3.0 * f


def _recsys_cell(spec, shape, mesh, multi_pod) -> Cell:
    cfg = spec.full_config()
    rules = sharding_rules(multi_pod)
    set_activation_rules(rules)
    ba = _batch_axes(multi_pod)
    boxed_and_offsets = jax.eval_shape(
        lambda: dcn.init(jax.random.PRNGKey(0), cfg)[0]
    )
    params, axes = split_boxed(boxed_and_offsets)
    pshard = _sanitize(params, shardings_from_axes(axes, mesh, rules), mesh)
    # offsets are tiny static metadata (field boundaries in the fused table)
    offsets = np.concatenate(
        [[0], np.cumsum(np.asarray(cfg.field_vocabs))[:-1]]
    ).astype(np.int32)
    offsets = jnp.asarray(offsets)
    dims = shape.dims
    B = dims["batch"]
    if B % _axes_size(mesh, ba) != 0:
        ba = None  # retrieval_cand: a single query replicates

    def make_batch(with_labels):
        b = {
            "dense": sds((B, cfg.n_dense), jnp.float32),
            "sparse": sds((B, cfg.n_sparse), jnp.int32),
        }
        s = {
            "dense": _ns(mesh, ba, None),
            "sparse": _ns(mesh, ba, None),
        }
        if with_labels:
            b["labels"] = sds((B,), jnp.float32)
            s["labels"] = _ns(mesh, ba)
        return b, s

    if shape.kind == "train":
        ocfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
        opt = jax.eval_shape(lambda p: adamw_init(p, ocfg), params)
        opt_shard = AdamWState(step=_ns(mesh), mu=pshard, nu=pshard)
        batch, bshard = make_batch(True)

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(dcn.loss_fn)(
                params, cfg, batch, offsets
            )
            new_p, new_o, gnorm = adamw_update(grads, opt, params, ocfg)
            return new_p, new_o, loss, gnorm

        return Cell(
            spec.arch_id, shape.name, "train", train_step,
            (params, opt, batch), (pshard, opt_shard, bshard),
            _dcn_flops(cfg, B),
            donate=(0, 1),
        )

    if shape.kind in ("serve", "bulk"):
        batch, bshard = make_batch(False)

        def serve_step(params, batch):
            return dcn.forward(params, cfg, batch, offsets)

        return Cell(
            spec.arch_id, shape.name, shape.kind, serve_step,
            (params, batch), (pshard, bshard),
            _dcn_flops(cfg, B, fwd_only=True),
        )

    assert shape.kind == "retrieval"
    # pad the candidate set to the device count (serving systems pad the
    # last ANN shard anyway); scores for pad rows are -inf'able downstream
    nc = _round_up(dims["n_candidates"], mesh.size)
    batch, bshard = make_batch(False)
    cand = sds((nc, cfg.retrieval_dim), jnp.float32)
    cand_shard = _ns(mesh, _all_axes(multi_pod), None)

    def retrieval_step(params, batch, cand):
        return dcn.retrieval_scores(params, cfg, batch, offsets, cand)

    flops = _dcn_flops(cfg, B, fwd_only=True) + 2.0 * B * nc * cfg.retrieval_dim
    return Cell(
        spec.arch_id, shape.name, "retrieval", retrieval_step,
        (params, batch, cand), (pshard, bshard, cand_shard), flops,
        notes=f"B={B} x {nc} candidates, batched dot + top_k",
    )


# =========================================================================
# paper engine (the paper's own contribution at published graph scale)
# =========================================================================

def _paper_cell(spec, shape, mesh, multi_pod,
                state_layout: str | None = None,
                or_impl: str | None = None) -> Cell:
    cfg = spec.full_config()
    dims = shape.dims
    n, avg_deg = dims["n_nodes"], dims["avg_degree"]
    sa = ("pod", "data") if multi_pod else ("data",)
    ga = ("model",)
    or_impl = or_impl or cfg.or_impl
    policy = POLICIES[cfg.policy](
        source_axes=sa, graph_axes=ga, or_impl=or_impl
    )
    shards = _axes_size(mesh, ga)
    n_pad = padded_n(n, shards, block=32)
    max_deg = cfg.max_deg_cap
    # memory-driven default: replicated per-node state for a 64-lane morsel
    # is 3·64 B/node (paper §4.2: 24 B packed; unpacked-lane tensor layout
    # trades 8x memory for MXU-shaped compute) — beyond ~40M nodes that
    # exceeds a 16 GB chip, switch to the sharded-state engine.
    if state_layout is None:
        lanes = policy.lanes if policy.is_multi_source else 1
        repl_bytes = n_pad * (3 * lanes + 4 * lanes)  # state + contribution
        state_layout = "sharded" if repl_bytes > 8e9 else "replicated"
    engine = build_engine(
        mesh, policy, cfg.edge_compute, n_pad, cfg.max_iters,
        state_layout=state_layout,
    )
    # the forward slabs as one bucket of width max_deg (the dry run has
    # no degree sequence to bin): rows_local x max_deg slots a shard, in
    # whole scan chunks
    rows_local = n_pad // shards
    chunks = -(-rows_local * max_deg // SCAN_CHUNK)
    slots = chunks * SCAN_CHUNK
    graph = GraphOperands(fwd=BinnedEll(
        nbrs=sds((shards, slots), jnp.int32),
        rows=sds((shards, slots), jnp.int32),
        perm=sds((shards, rows_local), jnp.int32),
        inv=sds((shards, rows_local), jnp.int32),
        degrees=sds((shards, rows_local), jnp.int32),
        chunk_lo=sds((shards, chunks), jnp.int32),
        chunk_hi=sds((shards, chunks), jnp.int32),
        shapes=((rows_local, max_deg),),
    ))
    src_shards = _axes_size(mesh, sa)
    morsels_np = pad_sources(
        np.arange(cfg.n_sources, dtype=np.int32), src_shards,
        policy.lanes, n_pad,
    )
    morsels = sds(morsels_np.shape, jnp.int32)
    lanes = policy.lanes
    # useful work: one edge visit per lane per scanned edge per iteration —
    # expected iterations ~ BFS diameter (cfg.max_iters caps it)
    edges_scanned = n * min(avg_deg, max_deg)
    flops = 2.0 * edges_scanned * lanes
    return Cell(
        spec.arch_id, f"{shape.name}", "query", engine.fn,
        (graph, morsels, sds((2,), jnp.float32)), None, flops,
        iters_scale=float(cfg.max_iters),
        notes=(
            f"policy={policy.name} or={or_impl} state={state_layout} "
            f"lanes={lanes} n_pad={n_pad} max_deg={max_deg}"
        ),
        prejitted=True,
    )


# =========================================================================
# entry point
# =========================================================================

def build_cell(arch_id: str, shape_name: str, mesh: Mesh, multi_pod: bool,
               **overrides) -> Cell:
    from ..models.gnn import common as gnn_common

    gnn_common.set_edge_slabs(None)  # GNN builders re-enable per mesh
    spec = cfgbase.get(arch_id)
    shape = {s.name: s for s in spec.shapes}[shape_name]
    if shape_name in spec.skips:
        raise ValueError(
            f"{arch_id} x {shape_name} is a documented skip: "
            f"{spec.skips[shape_name]}"
        )
    if spec.family == "lm":
        return _lm_cell(spec, shape, mesh, multi_pod)
    if spec.family == "gnn":
        return _gnn_cell(spec, shape, mesh, multi_pod)
    if spec.family == "recsys":
        return _recsys_cell(spec, shape, mesh, multi_pod)
    if spec.family == "paper":
        return _paper_cell(spec, shape, mesh, multi_pod, **overrides)
    raise ValueError(spec.family)


def lower_cell(cell: Cell, mesh: Mesh):
    """Lower (but do not compile) a cell under the mesh context."""
    if cell.prejitted:
        jf = cell.fn
    else:
        kw = {}
        if cell.out_shardings is not None:
            kw["out_shardings"] = cell.out_shardings
        jf = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            donate_argnums=cell.donate,
            **kw,
        )
    with jax.set_mesh(mesh):
        return jf.lower(*cell.args)
