"""Mixture-of-Experts FFN with three dispatch implementations.

* ``impl='einsum'``  — GShard-style one-hot dispatch/combine einsums with a
  per-expert capacity.  Robust and GSPMD-friendly, but dispatch flops scale
  as O(T * E*C * d) ≈ O(top_k * T^2 * d / tokens-per-expert) — visible as
  HLO_FLOPs above MODEL_FLOPS in the roofline table for large E (kimi
  baseline: useful ratio 0.05).
* ``impl='scatter'`` — position-computed scatter/gather dispatch under
  GSPMD: kills the dispatch flops but GSPMD partitions the scatters
  pathologically (§Perf kimi iteration 2: collective term 337 s → 2480 s).
  Kept as the measured negative result.
* ``impl='ep'``      — the §Perf winner: an explicit shard_map expert-
  parallel block. Local scatter dispatch (bytes, no GSPMD choice),
  all-to-all over the 'data' axis to the expert owners, expert FFN
  TP-sharded over 'model' (weights E→data, d_ff→model: FULLY sharded, no
  FSDP all-gather of the 2 TB expert bank), one psum over 'model', and the
  reverse all-to-all. Falls back to 'einsum' when the mesh lacks the axes
  (CPU smoke tests exercise it on a (1,1) mesh).

All compute experts as block-diagonal grouped matmuls and drop overflow
tokens beyond capacity (standard GShard semantics).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models.config import ArchConfig
from repro.models.layers import dense_init

Array = jax.Array


def moe_init(rng, cfg: ArchConfig) -> dict:
    mo = cfg.moe
    d, f = cfg.d_model, mo.d_ff_expert
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, mo.n_experts), dtype=jnp.float32),
        "w_up": dense_init(ks[1], (mo.n_experts, d, f), dtype=dt),
        "w_gate": dense_init(ks[2], (mo.n_experts, d, f), dtype=dt),
        "w_down": dense_init(ks[3], (mo.n_experts, f, d), dtype=dt),
    }
    if mo.n_shared_experts:
        fs = f * mo.n_shared_experts
        p["shared"] = {
            "w_up": dense_init(ks[4], (d, fs), dtype=dt),
            "w_gate": dense_init(jax.random.fold_in(ks[4], 1), (d, fs), dtype=dt),
            "w_down": dense_init(jax.random.fold_in(ks[4], 2), (fs, d), dtype=dt),
        }
    return p


def _router(p: dict, cfg: ArchConfig, x2d: Array):
    mo = cfg.moe
    logits = (x2d.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, mo.top_k)           # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return gate_vals, idx, probs


def _capacity(cfg: ArchConfig, T: int) -> int:
    mo = cfg.moe
    c = int(T * mo.top_k / mo.n_experts * mo.capacity_factor)
    return max(8, -(-c // 8) * 8)


def _experts_ffn(p: dict, cfg: ArchConfig, xe: Array) -> Array:
    """xe: (E, C, d) -> (E, C, d) block-diagonal grouped matmuls."""
    xe = shd.constrain(xe, ("experts", "expert_cap", None))
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    h = jax.nn.silu(g) * h
    h = shd.constrain(h, ("experts", "expert_cap", None))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _causal_positions(onehot: Array, counts0: Array | None = None
                      ) -> tuple[Array, Array]:
    """Per-(group, expert) capacity-slot positions, causal within each group.

    onehot: (G, S, K, E) int32 assignment one-hots.  The slot position of
    each assignment counts earlier assignments of the SAME group only,
    token-major then k-major — so the drop decision for token (g, s)
    depends exclusively on tokens (g, <= s), and a decode loop can
    reproduce it exactly from a running per-expert count (``counts0``, the
    counts carried in from previous tokens of the same sequence).

    Returns (pos (G, S, K), counts_end (G, E)).  Counts include dropped
    assignments — the parallel cumsum does too, so parity holds after
    capacity is exceeded.
    """
    G, S, K, E = onehot.shape
    flat = onehot.reshape(G, S * K, E)
    pos_in_e = jnp.cumsum(flat, axis=1).reshape(G, S, K, E) - 1
    if counts0 is not None:
        pos_in_e = pos_in_e + counts0[:, None, None, :]
    pos = jnp.sum(pos_in_e * onehot, axis=-1)
    counts_end = jnp.sum(flat, axis=1)
    if counts0 is not None:
        counts_end = counts_end + counts0
    return pos, counts_end


def _moe_einsum(p: dict, cfg: ArchConfig, x3d: Array) -> Array:
    """GShard one-hot dispatch over (G, S, d): G groups (batch rows), each
    with its own capacity C = _capacity(cfg, S) and causal slot positions
    (see ``_causal_positions`` — this is what makes decode reproducible)."""
    mo = cfg.moe
    G, S, d = x3d.shape
    E, K = mo.n_experts, mo.top_k
    C = _capacity(cfg, S)
    gate_vals, idx, _ = _router(p, cfg, x3d.reshape(G * S, d))
    gate_vals = gate_vals.reshape(G, S, K)

    onehot = jax.nn.one_hot(idx.reshape(G, S, K), E,
                            dtype=jnp.int32)                  # (G, S, K, E)
    pos, _ = _causal_positions(onehot)
    keep = pos < C
    # dispatch tensor (G, S, E, C): combines expert one-hot and slot.
    slot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                          dtype=x3d.dtype)[..., :C]           # (G, S, K, C)
    oh = onehot.astype(x3d.dtype)
    disp = jnp.einsum("gske,gskc->gsec", oh, slot)
    comb = jnp.einsum("gsk,gske,gskc->gsec",
                      gate_vals.astype(x3d.dtype), oh, slot)
    xe = jnp.einsum("gsd,gsec->egcd", x3d, disp)              # (E, G, C, d)
    ye = _experts_ffn(p, cfg, xe.reshape(E, G * C, d))
    ye = ye.reshape(E, G, C, d)
    return jnp.einsum("egcd,gsec->gsd", ye, comb)


def _moe_scatter(p: dict, cfg: ArchConfig, x3d: Array) -> Array:
    """Scatter/gather dispatch over (G, S, d) with the same per-group
    causal slot positions as ``_moe_einsum`` (identical keep sets)."""
    mo = cfg.moe
    G, S, d = x3d.shape
    E, K = mo.n_experts, mo.top_k
    C = _capacity(cfg, S)
    gate_vals, idx, _ = _router(p, cfg, x3d.reshape(G * S, d))

    flat_e = idx.reshape(G, S * K)                             # (G, SK)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (G, SK, E)
    pos = jnp.sum(onehot * (jnp.cumsum(onehot, axis=1) - 1), axis=-1)
    keep = pos < C                                             # (G, SK)
    pos_c = jnp.where(keep, pos, C - 1)
    g_idx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, S * K))
    tok_idx = jnp.broadcast_to(jnp.repeat(jnp.arange(S), K)[None],
                               (G, S * K))

    # Scatter tokens into (E, G, C, d) — bytes, not matmul flops.
    xe = jnp.zeros((E, G, C, d), x3d.dtype)
    upd = x3d[g_idx, tok_idx] * keep[..., None].astype(x3d.dtype)
    xe = xe.at[flat_e, g_idx, pos_c].add(upd)

    ye = _experts_ffn(p, cfg, xe.reshape(E, G * C, d))
    ye = ye.reshape(E, G, C, d)

    # Gather back and combine with gate weights.
    out_tk = ye[flat_e, g_idx, pos_c] * keep[..., None].astype(x3d.dtype)
    out_tk = out_tk * gate_vals.reshape(G, S * K, 1).astype(x3d.dtype)
    return jnp.zeros((G, S, d), x3d.dtype).at[g_idx, tok_idx].add(out_tk)


# ------------------------------------------------- explicit EP (shard_map) --
def _ep_local(x_loc: Array, router_w: Array, w_up: Array, w_gate: Array,
              w_down: Array, cfg: ArchConfig, *, axis_data, axis_model,
              n_data: int, n_model: int) -> Array:
    """Per-device body under shard_map (sequence-parallel EP + TP experts).

    x_loc: (T_loc, d) — a DISTINCT token slice per device (tokens split
           over data AND model: §Perf kimi iteration 4 — replicating the
           dispatch over 'model' cost a 16× larger all-to-all).
    w_*:   (E_loc, d, f_loc) / (E_loc, f_loc, d) — experts over 'data',
           d_ff over 'model'.

    Wire per device per call: 2 all-to-alls of (E, C, d) + one model-axis
    all-gather and one psum-scatter of the owner-row buffer — all sized by
    the actual dispatched tokens (T_loc·K·d·cf), never by the expert bank.
    """
    mo = cfg.moe
    T_loc, d = x_loc.shape
    E, K = mo.n_experts, mo.top_k
    E_loc = w_up.shape[0]
    C = _capacity(cfg, T_loc)

    gate_vals, idx, _ = _router({"router": router_w}, cfg, x_loc)

    # --- local scatter dispatch into (E, C, d): bytes, not matmul flops ---
    flat_e = idx.reshape(T_loc * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.sum(onehot * (jnp.cumsum(onehot, axis=0) - 1), axis=-1)
    keep = pos < C
    pos_c = jnp.where(keep, pos, C - 1)
    tok_idx = jnp.repeat(jnp.arange(T_loc), K)
    upd = x_loc[tok_idx] * keep[:, None].astype(x_loc.dtype)
    buf = jnp.zeros((E, C, d), x_loc.dtype).at[flat_e, pos_c].add(upd)

    # --- all-to-all over 'data': expert rows -> their owners --------------
    buf = buf.reshape(n_data, E_loc, C, d)
    recv = jax.lax.all_to_all(buf, axis_data, split_axis=0, concat_axis=0,
                              tiled=False)          # (n_data, E_loc, C, d)
    xe = jnp.moveaxis(recv, 0, 1).reshape(E_loc, n_data * C, d)

    # --- owner row: gather the 16 model columns' token sets, TP the FFN ---
    if n_model > 1:
        xe = jax.lax.all_gather(xe, axis_model, axis=1, tiled=True)
    # xe: (E_loc, n_model*n_data*C, d); each column computes its f_loc slice
    h = jnp.einsum("ecd,edf->ecf", xe, w_up)
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
    h = jax.nn.silu(g) * h
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)      # PARTIAL over 'model'
    if n_model > 1:
        # reduce over 'model' AND return each column its own token slice
        ye = jax.lax.psum_scatter(ye, axis_model, scatter_dimension=1,
                                  tiled=True)       # (E_loc, n_data*C, d)

    # --- reverse all-to-all + local combine --------------------------------
    ye = jnp.moveaxis(ye.reshape(E_loc, n_data, C, d), 1, 0)
    back = jax.lax.all_to_all(ye, axis_data, split_axis=0, concat_axis=0,
                              tiled=False)          # (n_data, E_loc, C, d)
    ye_loc = back.reshape(E, C, d)
    out_tk = ye_loc[flat_e, pos_c] * keep[:, None].astype(x_loc.dtype)
    out_tk = out_tk * gate_vals.reshape(T_loc * K, 1).astype(x_loc.dtype)
    return jnp.zeros((T_loc, d), x_loc.dtype).at[tok_idx].add(out_tk)


def _ep_decode_local(x_all: Array, router_w: Array, w_up: Array,
                     w_gate: Array, w_down: Array, cfg: ArchConfig, *,
                     axis_data, axis_model) -> Array:
    """Decode-time EP body: tokens REPLICATED (few at decode), experts
    sharded. Each device runs its local experts over every token, masked
    by the routing gates; one psum over (data, model) assembles the
    result. No dispatch, no all-to-all — wire cost is one (T, d) psum.
    """
    mo = cfg.moe
    T, d = x_all.shape
    E = mo.n_experts
    E_loc = w_up.shape[0]
    didx = jax.lax.axis_index(axis_data)

    gate_vals, idx, _ = _router({"router": router_w}, cfg, x_all)
    dense_gates = jnp.sum(
        jax.nn.one_hot(idx, E, dtype=x_all.dtype)
        * gate_vals[..., None].astype(x_all.dtype), axis=1)     # (T, E)
    my_gates = jax.lax.dynamic_slice_in_dim(dense_gates, didx * E_loc,
                                            E_loc, axis=1)      # (T, E_loc)

    h = jnp.einsum("td,edf->etf", x_all, w_up)
    g = jnp.einsum("td,edf->etf", x_all, w_gate)
    ye = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * h, w_down)
    y = jnp.einsum("etd,te->td", ye, my_gates)      # partial: local experts
    return jax.lax.psum(y, axis_data + (axis_model,))


# tokens-per-call threshold below which the replicated decode path wins
_EP_DECODE_MAX_TOKENS = 4096


def _moe_ep(p: dict, cfg: ArchConfig, x: Array) -> Array | None:
    """x: (B, T, d). Returns None when the mesh/shapes can't EP.

    The shard_map consumes the NATURAL activation layout — batch over
    'data', seq over 'model' (sequence parallelism) — so entering the
    region is a local slice.  (Fusing (B·T) rows and resharding instead
    triggered GSPMD's 'involuntary full rematerialization' path: the whole
    activation was replicated per layer; §Perf kimi iteration 4.)
    """
    mesh = shd.get_mesh()
    axes = set(mesh.axis_names)
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    n_model = mesh.shape.get("model", 1) if "model" in axes else 1
    B, T, d = x.shape
    if cfg.moe.n_experts % max(n_data, 1):
        return None                                 # mesh can't EP

    # Decode / tiny-token path: replicated tokens, local-expert compute.
    if B * T <= _EP_DECODE_MAX_TOKENS or B % max(n_data, 1):
        body = partial(_ep_decode_local, cfg=cfg, axis_data=data_axes,
                       axis_model="model")

        def body3d(x_rep, router_w, w_up, w_gate, w_down):
            return body(x_rep.reshape(B * T, d), router_w, w_up, w_gate,
                        w_down).reshape(B, T, d)

        fn = jax.shard_map(
            body3d, mesh=mesh,
            in_specs=(P(None, None, None),          # x replicated
                      P(),
                      P(data_axes, None, "model"),
                      P(data_axes, None, "model"),
                      P(data_axes, "model", None)),
            out_specs=P(None, None, None),
            check_vma=False)
        return fn(x, p["router"], p["w_up"], p["w_gate"], p["w_down"])

    def body(x_loc, router_w, w_up, w_gate, w_down):
        Bl, Tl, _ = x_loc.shape
        y = _ep_local(x_loc.reshape(Bl * Tl, d), router_w, w_up, w_gate,
                      w_down, cfg, axis_data=data_axes, axis_model="model",
                      n_data=n_data, n_model=n_model)
        return y.reshape(Bl, Tl, d)

    seq_axis = "model" if (n_model > 1 and T % n_model == 0) else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axes, seq_axis, None),     # x: batch×seq split
                  P(),                              # router (replicated)
                  P(data_axes, None, "model"),      # w_up
                  P(data_axes, None, "model"),      # w_gate
                  P(data_axes, "model", None)),     # w_down
        out_specs=P(data_axes, seq_axis, None),
        check_vma=False)
    return fn(x, p["router"], p["w_up"], p["w_gate"], p["w_down"])


def _shared_experts(p: dict, x2d: Array) -> Array:
    sp = p["shared"]
    h = jax.nn.silu(x2d @ sp["w_gate"]) * (x2d @ sp["w_up"])
    return h @ sp["w_down"]


def moe_apply(p: dict, cfg: ArchConfig, x: Array) -> Array:
    B, T, d = x.shape
    x2d = x.reshape(B * T, d)
    impl = cfg.moe.impl
    y = None
    if impl == "ep" and shd.get_mesh() is not None:
        y3d = _moe_ep(p, cfg, x)
        y = None if y3d is None else y3d.reshape(B * T, d)
    if y is None:
        # einsum/scatter dispatch groups = batch rows: capacity is per
        # sequence and slot positions are causal within it, so a decode
        # loop with a count cache reproduces the drops exactly.
        if impl == "scatter":
            y = _moe_scatter(p, cfg, x).reshape(B * T, d)
        else:
            y = _moe_einsum(p, cfg, x).reshape(B * T, d)
    if cfg.moe.n_shared_experts:
        y = y + _shared_experts(p, x2d)
    return y.reshape(B, T, d)


# ------------------------------------------------------------- decode ------
def moe_cache_init(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    """Per-sequence decode state: running per-expert assignment counts and
    the capacity the parallel path would use for a ``max_seq`` sequence.

    The einsum/scatter paths drop tokens by causal per-row slot position,
    so decode parity just needs the count each row's earlier tokens (and
    earlier k-slots of the same token) contributed per expert — PLUS a
    matching capacity: decode replays a T-token parallel pass exactly iff
    ``_capacity(cfg, max_seq) == _capacity(cfg, T)`` (init the caches
    with ``max_seq`` equal to the sequence length being compared; a
    serving loop that only ever decodes just needs ONE consistent
    capacity, which ``max_seq`` provides).
    """
    return {
        "counts": jnp.zeros((batch, cfg.moe.n_experts), jnp.int32),
        "capacity": jnp.asarray(_capacity(cfg, max_seq), jnp.int32),
    }


def moe_decode(p: dict, cfg: ArchConfig, x: Array, cache: dict
               ) -> tuple[Array, dict]:
    """One decode chunk x: (B, S, d) (S is typically 1) through the MoE FFN.

    Matches ``moe_apply`` on the einsum/scatter paths token-for-token: the
    router and gates are identical per token, and the capacity-drop
    decision replays the parallel path's causal slot positions from the
    cached counts (given the capacity contract in ``moe_cache_init``).
    The expert compute itself is dense over the few decode tokens (the
    ``_ep_decode_local`` trick) — at S·B tokens the dispatch machinery
    costs more than it saves.  Includes the shared experts.
    """
    mo = cfg.moe
    B, S, d = x.shape
    E, K = mo.n_experts, mo.top_k
    x2d = x.reshape(B * S, d)
    gate_vals, idx, _ = _router(p, cfg, x2d)
    onehot = jax.nn.one_hot(idx.reshape(B, S, K), E, dtype=jnp.int32)
    pos, counts = _causal_positions(onehot, cache["counts"])
    keep = pos < cache["capacity"]                           # (B, S, K)
    gates = jnp.einsum(
        "bsk,bske->bse",
        jnp.where(keep, gate_vals.reshape(B, S, K), 0.0).astype(x.dtype),
        onehot.astype(x.dtype))                              # dense (B,S,E)

    h = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    g = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
    ye = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * h, p["w_down"])
    y = jnp.einsum("bsed,bse->bsd", ye, gates)
    if mo.n_shared_experts:
        y = y + _shared_experts(p, x2d).reshape(B, S, d)
    return y, {"counts": counts, "capacity": cache["capacity"]}
