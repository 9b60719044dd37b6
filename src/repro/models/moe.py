"""Top-k routed MoE (granite 32e/top-8, qwen3 128e/top-8).

Execution strategy (TPU-native, DESIGN.md §5): tokens stay data-sharded,
experts shard over the ``model`` axis.  Each model shard routes *locally*:
for its expert slice it picks the top-C tokens by gate weight (capacity-based
token-choice with gate-priority dropping, GShard semantics), gathers them,
runs the batched expert GEMM ``[E_loc, C, d] x [E_loc, d, f]``, and
scatter-adds the weighted outputs.  Merging expert contributions is a single
psum over ``model`` — the same volume as a Megatron MLP all-reduce, so MoE
adds **no** extra collective class (no all-to-all needed at this sharding).

Two entry points with identical math:
- :func:`moe_apply` — pure jnp (all experts local; smoke tests, oracle);
- :func:`moe_apply_sharded` — shard_map over (fsdp x model) for the
  production mesh.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init

__all__ = ["moe_params", "moe_apply", "moe_apply_sharded", "moe_reference"]


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def moe_params(cfg) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": dense_init((d, "embed"), (e, None)),
        "wi": dense_init((e, "expert"), (d, "embed"), (f, None)),
        "wg": dense_init((e, "expert"), (d, "embed"), (f, None)),
        "wo": dense_init((e, "expert"), (f, None), (d, "embed")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared_wi"] = dense_init((d, "embed"), (fs, "mlp"))
        p["shared_wg"] = dense_init((d, "embed"), (fs, "mlp"))
        p["shared_wo"] = dense_init((fs, "mlp"), (d, "embed"))
    return p


def _route(cfg, x: jnp.ndarray, router_w: jnp.ndarray) -> jnp.ndarray:
    """x [T,d] -> dense gate matrix [T,E]: softmax over each token's top-k
    logits, zero elsewhere (token-choice routing)."""
    logits = (x @ router_w).astype(jnp.float32)           # [T, E]
    k = cfg.experts_per_token
    vals, idx = jax.lax.top_k(logits, k)                  # [T, k]
    gates = jax.nn.softmax(vals, axis=-1)
    onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", gates, onehot)        # [T, E]


def _expert_compute(cfg, x: jnp.ndarray, gate_slice: jnp.ndarray,
                    wi: jnp.ndarray, wg: jnp.ndarray, wo: jnp.ndarray,
                    capacity: int) -> jnp.ndarray:
    """Capacity-C gather/GEMM/scatter for a slice of experts.

    x [T,d]; gate_slice [T,E_loc]; wi/wg [E_loc,d,f]; wo [E_loc,f,d].
    """
    t = x.shape[0]
    c = min(capacity, t)
    vals, tok = jax.lax.top_k(gate_slice.T, c)            # [E_loc, C]
    live = vals > 0.0
    xg = jnp.take(x, tok.reshape(-1), axis=0).reshape(
        tok.shape[0], c, x.shape[1])                       # [E_loc, C, d]
    h = jnp.einsum("ecd,edf->ecf", xg, wi,
                   preferred_element_type=jnp.float32)
    h = h * jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg,
                                   preferred_element_type=jnp.float32))
    y = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), wo,
                   preferred_element_type=jnp.float32)
    y = y * (vals * live)[..., None]
    out = jnp.zeros((t, x.shape[1]), jnp.float32)
    out = out.at[tok.reshape(-1)].add(y.reshape(-1, x.shape[1]),
                                      mode="drop")
    return out


def _capacity(cfg, tokens: int, capacity_factor: float) -> int:
    per = tokens * cfg.experts_per_token / max(cfg.n_experts, 1)
    return max(1, int(per * capacity_factor + 0.999))


def _shared(cfg, p, x):
    h = x @ p["shared_wi"]
    h = jax.nn.silu(x @ p["shared_wg"]) * h
    return h @ p["shared_wo"]


def moe_apply(cfg, p: Dict, x: jnp.ndarray,
              capacity_factor: float = 2.0) -> jnp.ndarray:
    """Unsharded path: x [B,S,d] -> [B,S,d]."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    gates = _route(cfg, xf, p["router"])
    cap = _capacity(cfg, xf.shape[0], capacity_factor)
    out = _expert_compute(cfg, xf, gates, p["wi"], p["wg"], p["wo"], cap)
    out = out.reshape(b, s, d).astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out


def moe_apply_sharded(cfg, p: Dict, x: jnp.ndarray, mesh,
                      data_axes: Tuple[str, ...],
                      model_axis: str = "model",
                      capacity_factor: float = 1.25) -> jnp.ndarray:
    """Expert-parallel path under shard_map (see module docstring)."""
    n_model = mesh.shape[model_axis]
    assert cfg.n_experts % n_model == 0, \
        f"{cfg.n_experts} experts not divisible by model={n_model}"
    e_loc = cfg.n_experts // n_model

    def block(xb, router_w, wi, wg, wo):
        b, s, d = xb.shape
        xf = xb.reshape(-1, d)
        gates = _route(cfg, xf, router_w)                  # [T_loc, E]
        shard = jax.lax.axis_index(model_axis)
        gate_slice = jax.lax.dynamic_slice_in_dim(
            gates, shard * e_loc, e_loc, axis=1)
        cap = _capacity(cfg, xf.shape[0], capacity_factor)
        out = _expert_compute(cfg, xf, gate_slice, wi, wg, wo, cap)
        out = jax.lax.psum(out, model_axis)
        return out.reshape(b, s, d).astype(xb.dtype)

    spec_x = P(data_axes, None, None)
    spec_e = P(model_axis, None, None)
    out = _shard_map(
        block, mesh,
        in_specs=(spec_x, P(None, None), spec_e, spec_e, spec_e),
        out_specs=spec_x,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out


def moe_apply_sharded_a2a(cfg, p: Dict, x: jnp.ndarray, mesh,
                          data_axes: Tuple[str, ...],
                          model_axis: str = "model",
                          capacity_factor: float = 1.25) -> jnp.ndarray:
    """All-to-all expert parallelism (GShard/Switch dispatch).

    Contrast with :func:`moe_apply_sharded` (psum design): here tokens shard
    over BOTH data and model axes (sequence over model), each device routes
    only its own tokens and exchanges per-expert blocks with two
    ``all_to_all``s.  Wire bytes per device ≈ 2·T_dev·k·cf·d vs the psum
    design's all-gather+reduce ≈ 4·T_loc·d — a2a wins when
    k·cf/n_model < 2, i.e. for fine-grained MoEs on wide meshes (qwen3:
    k=8, cf=1.25, n_model=16 ⇒ ~3× fewer bytes).  Dry-run flag:
    ``--moe-a2a``.
    """
    n_model = mesh.shape[model_axis]
    assert cfg.n_experts % n_model == 0
    e_loc = cfg.n_experts // n_model
    d = x.shape[-1]
    if x.shape[1] % n_model != 0:     # e.g. decode (S=1): psum path instead
        return moe_apply_sharded(cfg, p, x, mesh, data_axes, model_axis,
                                 capacity_factor)

    def block(xb, router_w, wi, wg, wo):
        b, s, _ = xb.shape
        xf = xb.reshape(-1, d)                      # [T_dev, d]
        gates = _route(cfg, xf, router_w)           # [T_dev, E]
        cap = _capacity(cfg, xf.shape[0], capacity_factor)
        cap = min(cap, xf.shape[0])
        vals, tok = jax.lax.top_k(gates.T, cap)     # [E, C] per-expert picks
        live = vals > 0.0
        xg = jnp.take(xf, tok.reshape(-1), axis=0) \
            .reshape(cfg.n_experts, cap, d)         # [E, C, d]
        send = xg.reshape(n_model, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0)    # [n_model, e_loc, C, d]
        toks = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_model * cap, d)
        h = jnp.einsum("ecd,edf->ecf", toks, wi,
                       preferred_element_type=jnp.float32)
        h = h * jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, wg,
                                       preferred_element_type=jnp.float32))
        y = jnp.einsum("ecf,efd->ecd", h.astype(xb.dtype), wo,
                       preferred_element_type=jnp.float32)
        y = y.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, model_axis, split_axis=0,
                                  concat_axis=0)    # [n_model, e_loc, C, d]
        y_local = back.reshape(cfg.n_experts, cap, d)
        y_local = y_local * (vals * live)[..., None]
        out = jnp.zeros((xf.shape[0], d), jnp.float32)
        out = out.at[tok.reshape(-1)].add(
            y_local.reshape(-1, d), mode="drop")
        return out.reshape(b, s, d).astype(xb.dtype)

    spec_x = P(data_axes, model_axis, None)
    spec_e = P(model_axis, None, None)
    out = _shard_map(
        block, mesh,
        in_specs=(spec_x, P(None, None), spec_e, spec_e, spec_e),
        out_specs=spec_x,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out


def moe_reference(cfg, p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """Exact (no-capacity) oracle: y_t = sum_e g_te FFN_e(x_t)."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    gates = _route(cfg, xf, p["router"])                   # [T, E]
    h = jnp.einsum("td,edf->tef", xf, p["wi"])
    h = h * jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["wg"]))
    y = jnp.einsum("tef,efd->ted", h, p["wo"])
    out = jnp.einsum("te,ted->td", gates, y)
    out = out.reshape(b, s, d).astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + _shared(cfg, p, x)
    return out
