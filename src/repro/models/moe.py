"""Mixture-of-Experts FFN with top-k routing, in two forms.

Serving (`Model.prefill` and `decode_step`) uses `moe_ffn_dropless`: an
expert layer told which experts it holds (`cfg.expert_offset`,
`cfg.num_experts` of the `cfg.routed_experts` the router scores). It
routes every token over all routed experts, sorts the (token, choice)
slots that fall on a held expert by that expert, and runs the held
experts' SwiGLU as grouped matmuls (the Megablox Pallas kernel, `gmm`)
over exactly those rows: no capacity, no drops, and nothing computed
for a slot routed elsewhere or for an empty group. What the absent experts would
add is left to the chips that hold them (expert parallelism); on one
chip the layer runs without that exchange. It returns routing counts
of the held experts, which the serving engine accumulates.

Training (`Model.loss`) keeps the *sorted-capacity* formulation: per
batch row, token slots are sorted by expert id, each expert processes
a fixed-capacity contiguous buffer, and results scatter back weighted
by the router gate. All shapes are static (jit-friendly); tokens beyond
capacity are dropped (capacity_factor 1.25, like GShard/Switch).
Sorting stays local to the batch row, so under batch->data sharding the
dispatch never crosses data shards; expert weights are sharded on d_ff
over the model axis (tensor-parallel experts), which keeps every
expert-count (40, 8) legal on a 16-way axis — see DESIGN.md
§Distribution design.

Both share the gate: float32 softmax over the routed experts, greedy
top-k, renormalisation of the k gates where `cfg.moe_renormalize`, and
`cfg.moe_gate_scale` (DeepSeek's routed scaling factor). Shared experts
(`shared_ffn`) are added by the caller in either form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

from repro.models.config import ModelConfig


def router_probs(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """(...,D) x (D,E) -> (...,E) softmax router probabilities (fp32)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def gate_weights(gate: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The top-k router probabilities as combine weights."""
    if cfg.moe_renormalize:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_gate_scale != 1.0:
        gate = gate * cfg.moe_gate_scale
    return gate


def held_expert(expert_idx: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Each routed expert id as an index into the held expert stack,
    with `cfg.num_experts` standing for an expert held elsewhere."""
    local = expert_idx - cfg.expert_offset
    held = (local >= 0) & (local < cfg.num_experts)
    return jnp.where(held, local, cfg.num_experts)


def shared_ffn(x: jax.Array, params: dict) -> jax.Array | float:
    """The always-on shared experts, one SwiGLU (0.0 where the layer has
    none)."""
    sp = params.get("shared")
    if sp is None:
        return 0.0
    g = jnp.einsum("...d,df->...f", x, sp["w_gate"])
    u = jnp.einsum("...d,df->...f", x, sp["w_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, sp["w_down"])


def zero_counts(cfg: ModelConfig) -> dict:
    """Routing counts of the held experts: tokens per held expert and
    non-empty (layer, held expert) groups."""
    return {"tokens": jnp.zeros((cfg.num_experts,), jnp.int32),
            "groups": jnp.zeros((), jnp.int32)}


def add_counts(total, counts):
    if total is None or counts is None:
        return total
    return jax.tree.map(jnp.add, total, counts)


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Megablox tiles (rows, contraction, output columns): 512-row tiles
    for prompts, 128 for a decode step's few rows; the contraction in
    512s; a whole expert width (1,408) per output tile, else 1,024.
    Measured on one v5e chip at the DeepSeek-V2-Lite widths (PERF.md)."""
    tm = 512 if m >= 2048 else 128
    return tm, min(512, k), n if n <= 1536 else 1024


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array
                   ) -> jax.Array:
    """Rows of `lhs` (M, K), sorted by group, times their group's matrix
    of `rhs` (G, K, N): the Megablox grouped matmul (a Pallas kernel,
    `gmm` in the device trace). `group_sizes` has G + 1 entries; the
    last group's rows (those routed elsewhere) are not computed and
    come back zero, as do the rows past them. Groups with no rows are
    skipped. M is padded to a multiple of the row tile."""
    m, k = lhs.shape
    tiling = _gmm_tiling(m, k, rhs.shape[-1])
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    out = gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
              tiling=tiling, interpret=jax.default_backend() != "tpu")
    return out[:m]


def moe_ffn_dropless(x: jax.Array, params: dict, cfg: ModelConfig,
                     valid: jax.Array | None = None,
                     layer: jax.Array | None = None
                     ) -> tuple[jax.Array, dict]:
    """Dropless top-k MoE SwiGLU over the held experts.

    x: (B,S,D); `valid` (B,) marks the rows whose tokens are routed at
    all (the engine's live slots). The expert matrices are one layer's
    (n, D, F) or, with `layer`, the whole stack (L, n, D, F), read in
    place by the grouped matmul (a scan slicing them out would copy every
    layer's experts, empty or not). Returns (the held experts' part of
    the output (B,S,D), their routing counts, `zero_counts` shaped)."""
    b, s, d = x.shape
    n, k = cfg.num_experts, cfg.experts_per_token
    xt = x.reshape(b * s, d)
    probs = router_probs(xt, params["router"])                  # (T,E)
    gate, expert_idx = jax.lax.top_k(probs, k)                  # (T,k)
    gate = gate_weights(gate, cfg)
    group = held_expert(expert_idx, cfg)
    if valid is not None:
        live = jnp.repeat(valid.astype(bool), s)[:, None]
        group = jnp.where(live, group, n)
    group = group.reshape(-1)                                   # (T*k,)
    # counting sort by group, stable in slot order: `dest` is each
    # slot's row among the sorted rows, `order` the slot of each row
    onehot = jax.nn.one_hot(group, n + 1, dtype=jnp.int32)      # (T*k, n+1)
    sizes = onehot.sum(0)
    rank = jnp.sum(jnp.cumsum(onehot, 0) * onehot, 1) - 1
    dest = (jnp.cumsum(sizes) - sizes)[group] + rank
    order = jnp.zeros_like(group).at[dest].set(
        jnp.arange(group.shape[0], dtype=group.dtype))
    held = sizes[:n]
    w = {name: params[name] for name in ("w_gate", "w_up", "w_down")}
    if layer is not None:           # groups of the other layers are empty
        n_layers = w["w_gate"].shape[0]
        w = {name: a.reshape(n_layers * n, *a.shape[2:])
             for name, a in w.items()}
        sizes = jnp.zeros((n_layers * n + 1,), jnp.int32).at[
            layer * n + jnp.arange(n)].set(held).at[-1].set(sizes[n])
    rows = xt[order // k]                           # held rows first
    g = grouped_matmul(rows, w["w_gate"], sizes)
    u = grouped_matmul(rows, w["w_up"], sizes)
    act = (jax.nn.silu(g) * u).astype(x.dtype)
    out = grouped_matmul(act, w["w_down"], sizes)
    # back to slot order; rows routed elsewhere are zero and weigh zero
    weight = jnp.where(group < n, gate.reshape(-1), 0.0)
    y = jnp.sum((out[dest] * weight[:, None]).reshape(b * s, k, d), 1)
    counts = {"tokens": held, "groups": jnp.sum(held > 0, dtype=jnp.int32)}
    return y.astype(x.dtype).reshape(b, s, d), counts


def load_balance_loss(probs: jax.Array, expert_idx: jax.Array,
                      num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * P_e."""
    # fraction of slots dispatched to each expert
    counts = jnp.sum(jax.nn.one_hot(expert_idx, num_experts), axis=(1, 2))
    f = counts / jnp.maximum(jnp.sum(counts, -1, keepdims=True), 1.0)  # (B,E)
    p = jnp.mean(probs, axis=1)                                        # (B,E)
    return num_experts * jnp.mean(jnp.sum(f * p, axis=-1))


def moe_ffn(x: jax.Array, params: dict, cfg: ModelConfig
            ) -> tuple[jax.Array, jax.Array]:
    """Top-k MoE SwiGLU FFN. x: (B,S,D) -> (y (B,S,D), aux_loss ())."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    probs = router_probs(x, params["router"])                   # (B,S,E)
    gate, expert_idx = jax.lax.top_k(probs, k)                  # (B,S,k)
    gate = gate_weights(gate, cfg)
    aux = load_balance_loss(probs, expert_idx, cfg.routed_experts)

    n_slots = s * k
    capacity = max(1, min(
        -(-int(n_slots * cfg.moe_capacity_factor)                # ceil division
          // cfg.routed_experts),
        n_slots))

    # --- per-row sorted dispatch (experts held elsewhere sort last) --- #
    e_flat = held_expert(expert_idx, cfg).reshape(b, n_slots)   # (B, S*k)
    gate_flat = gate.reshape(b, n_slots)
    tok_of_slot = jnp.repeat(jnp.arange(s), k)[None, :]         # (1, S*k)
    order = jnp.argsort(e_flat, axis=-1, stable=True)           # (B, S*k)
    e_sorted = jnp.take_along_axis(e_flat, order, axis=-1)
    gate_sorted = jnp.take_along_axis(gate_flat, order, axis=-1)
    tok_sorted = jnp.take_along_axis(
        jnp.broadcast_to(tok_of_slot, (b, n_slots)), order, axis=-1)

    # position of each slot within its expert's buffer
    first = jax.vmap(
        lambda row: jnp.searchsorted(row, row, side="left"))(e_sorted)
    pos = jnp.arange(n_slots)[None, :] - first                  # (B, S*k)
    keep = (pos < capacity) & (e_sorted < e)
    dest = jnp.where(keep, e_sorted * capacity + pos, e * capacity)

    # gather token activations into expert buffers (B, E*C+1, D)
    x_slot = jnp.take_along_axis(x, tok_sorted[..., None], axis=1)
    buf = jnp.zeros((b, e * capacity + 1, d), x.dtype)
    buf = buf.at[jnp.arange(b)[:, None], dest].add(
        jnp.where(keep[..., None], x_slot, 0))
    buf = buf[:, : e * capacity].reshape(b, e, capacity, d)

    # --- expert computation (SwiGLU, experts sharded on d_ff) --------- #
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, params["w_up"])
    y_buf = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, params["w_down"])

    # --- combine back ------------------------------------------------- #
    y_buf = y_buf.reshape(b, e * capacity, d)
    y_buf = jnp.concatenate([y_buf, jnp.zeros((b, 1, d), y_buf.dtype)], 1)
    y_slot = jnp.take_along_axis(y_buf, dest[..., None], axis=1)  # (B,S*k,D)
    y_slot = y_slot * (gate_sorted * keep)[..., None].astype(y_buf.dtype)
    y = jnp.zeros((b, s, d), x.dtype)
    y = y.at[jnp.arange(b)[:, None], tok_sorted].add(y_slot)
    return y, aux


def moe_ffn_sharded(x: jax.Array, params: dict, cfg: ModelConfig
                    ) -> tuple[jax.Array, jax.Array]:
    """SPMD-safe MoE: the sorted-capacity dispatch runs inside shard_map
    so sorts/gathers/scatters stay device-local (GSPMD otherwise lifts the
    data-dependent scatter to a full batch all-gather — measured 14x FLOP
    replication on mixtral train_4k, see EXPERIMENTS.md §Dry-run).

    Batch stays sharded over (pod, data); expert weights are sharded on
    d_ff over `model`; the w_down contraction finishes with a psum over
    `model` — the same collective a dense row-parallel MLP needs.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return moe_ffn(x, params, cfg)
    b = x.shape[0]
    f = cfg.d_ff
    bax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    axsz = 1
    for a in bax:
        axsz *= mesh.shape[a]
    if not bax or b % axsz:
        bax = ()
    f_ok = f % mesh.shape["model"] == 0
    f_ax = "model" if f_ok else None
    P = jax.sharding.PartitionSpec
    bspec = P(bax if bax else None, None, None)

    def local_fn(x_l, router, wg, wu, wd):
        y, aux = moe_ffn(x_l, {"router": router, "w_gate": wg, "w_up": wu,
                               "w_down": wd}, cfg)
        if f_ax:
            y = jax.lax.psum(y, f_ax)
        if bax:
            aux = jax.lax.pmean(aux, bax)
        return y, aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(bspec, P(None, None), P(None, None, f_ax),
                  P(None, None, f_ax), P(None, f_ax, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return y, aux


def moe_ffn_dense_reference(x: jax.Array, params: dict, cfg: ModelConfig
                            ) -> tuple[jax.Array, jax.Array]:
    """Oracle: compute EVERY held expert densely and combine by gates
    (no capacity drops). Used by tests; O(E/k) more FLOPs than moe_ffn."""
    e, k = cfg.num_experts, cfg.experts_per_token
    probs = router_probs(x, params["router"])
    gate, expert_idx = jax.lax.top_k(probs, k)
    gate = gate_weights(gate, cfg)
    aux = load_balance_loss(probs, expert_idx, cfg.routed_experts)
    # (B,S,E) combine weights (zero for non-selected experts and for
    # experts held elsewhere, whose one-hot row is empty)
    combine = jnp.sum(jax.nn.one_hot(held_expert(expert_idx, cfg), e)
                      * gate[..., None], axis=2)
    g = jnp.einsum("bsd,edf->bsef", x, params["w_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, params["w_up"])
    y_all = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, params["w_down"])
    y = jnp.einsum("bsed,bse->bsd", y_all, combine.astype(x.dtype))
    return y, aux
