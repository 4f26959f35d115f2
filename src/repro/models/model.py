"""Config-driven model zoo: init / train-loss / prefill / decode for every
architecture in `repro.configs` (dense GQA, MLA, MoE+SWA, DeepSeek-style
MoE with shared experts and leading dense layers, Mamba2 SSD, Zamba2
hybrid, Seamless enc-dec audio, InternVL2 VLM).

Conventions:
  * params are plain nested dicts; per-layer params are stacked on a
    leading `num_layers` axis and iterated with lax.scan (compact HLO for
    the 80 dry-run compiles). A model with `first_dense_layers` has two
    stacks: `dense_layers` (dense FFN) and then `layers` (MoE).
  * caches are dicts of stacked arrays with a scalar `pos` (valid tokens);
    the `dense_layers` stack's entries carry the prefix `dense_`. For an
    MoE model, `prefill` and `decode_step` also return the call's routing
    counts of the held experts under `moe` (see `moe.zero_counts`).
  * modality frontends (ViT / audio codec) are STUBS per the assignment:
    callers pass precomputed `embeds` of shape (B, frontend_tokens, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.config import ModelConfig
from repro.models.config import yarn_mscale
from repro.models.layers import (apply_rope, cross_entropy_loss, embed,
                                 rms_norm, swiglu, unembed, yarn_frequencies)

INIT_STD = 0.02
MOE_AUX_COEF = 0.01


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===================================================================== #
# initialization
# ===================================================================== #
def _dense(key, shape, dtype, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _init_attn(key, cfg: ModelConfig, dt) -> dict:
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    if cfg.attn_type == "mla":
        qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
        ks = jax.random.split(key, 6)
        if cfg.q_lora_rank:
            q = {"q_down": _dense(ks[0], (d, cfg.q_lora_rank), dt),
                 "q_ln": jnp.ones((cfg.q_lora_rank,), dt),
                 "q_up": _dense(ks[1], (cfg.q_lora_rank, h * qk_dim), dt)}
        else:                      # queries straight from x (DeepSeek-V2-Lite)
            q = {"wq": _dense(ks[1], (d, h * qk_dim), dt)}
        return {
            **q,
            "kv_down": _dense(ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim), dt),
            "kv_ln": jnp.ones((cfg.kv_lora_rank,), dt),
            "k_up": _dense(ks[3], (cfg.kv_lora_rank, h * cfg.qk_nope_dim), dt),
            "v_up": _dense(ks[4], (cfg.kv_lora_rank, h * cfg.v_head_dim), dt),
            "wo": _dense(ks[5], (h * cfg.v_head_dim, d), dt),
        }
    return {
        "wq": _dense(ks[0], (d, h * hd), dt),
        "wk": _dense(ks[1], (d, hkv * hd), dt),
        "wv": _dense(ks[2], (d, hkv * hd), dt),
        "wo": _dense(ks[3], (h * hd, d), dt),
    }


def _init_mlp(key, cfg: ModelConfig, dt, dense: bool = False) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    if cfg.num_experts and not dense:
        e, f = cfg.num_experts, cfg.moe_d_ff
        p = {
            "router": _dense(ks[0], (d, cfg.routed_experts), jnp.float32),
            "w_gate": _dense(ks[1], (e, d, f), dt),
            "w_up": _dense(ks[2], (e, d, f), dt),
            "w_down": _dense(ks[3], (e, f, d), dt),
        }
        if cfg.shared_experts:
            fs = cfg.shared_experts * f
            kg, ku, kd = jax.random.split(jax.random.fold_in(key, 1), 3)
            p["shared"] = {"w_gate": _dense(kg, (d, fs), dt),
                           "w_up": _dense(ku, (d, fs), dt),
                           "w_down": _dense(kd, (fs, d), dt)}
        return p
    return {
        "w_gate": _dense(ks[0], (d, f), dt),
        "w_up": _dense(ks[1], (d, f), dt),
        "w_down": _dense(ks[2], (f, d), dt),
    }


def _init_block(key, cfg: ModelConfig, dt, cross: bool = False,
                dense: bool = False) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dt),
        "attn": _init_attn(k1, cfg, dt),
        "ln2": jnp.ones((cfg.d_model,), dt),
        "mlp": _init_mlp(k2, cfg, dt, dense),
    }
    if cross:
        p["ln_x"] = jnp.ones((cfg.d_model,), dt)
        p["xattn"] = _init_attn(k3, cfg, dt)
    return p


def _init_mamba(key, cfg: ModelConfig, dt) -> dict:
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    w = cfg.ssm_conv_width
    ks = jax.random.split(key, 7)
    return {
        "ln": jnp.ones((d,), dt),
        "w_z": _dense(ks[0], (d, di), dt),
        "w_x": _dense(ks[1], (d, di), dt),
        "w_b": _dense(ks[2], (d, n), dt),
        "w_c": _dense(ks[3], (d, n), dt),
        "w_dt": _dense(ks[4], (d, h), dt),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "conv": _dense(ks[5], (w, di + 2 * n), dt, std=0.2),
        "a_log": jnp.zeros((h,), jnp.float32),      # A = -1
        "d_skip": jnp.ones((h,), jnp.float32),
        "gate_ln": jnp.ones((di,), dt),
        "w_out": _dense(ks[6], (di, d), dt),
    }


class Model:
    """Family-dispatched functional model. All methods are jit-safe."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------ init ----------------------------- #
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        k_emb, k_un, k_layers, k_extra = jax.random.split(key, 4)
        params: dict = {
            "embed": _dense(k_emb, (cfg.padded_vocab, cfg.d_model), dt),
            "unembed": _dense(k_un, (cfg.padded_vocab, cfg.d_model), dt),
            "final_ln": jnp.ones((cfg.d_model,), dt),
        }
        lk = jax.random.split(k_layers, cfg.num_layers)
        if cfg.family == "ssm":
            params["layers"] = jax.vmap(
                lambda k: _init_mamba(k, cfg, dt))(lk)
        elif cfg.family == "hybrid":
            params["layers"] = jax.vmap(
                lambda k: _init_mamba(k, cfg, dt))(lk)
            params["shared"] = _init_block(k_extra, cfg, dt)
        elif cfg.is_encdec:
            params["layers"] = jax.vmap(
                lambda k: _init_block(k, cfg, dt, cross=True))(lk)
            ek = jax.random.split(k_extra, cfg.encoder_layers)
            params["enc_layers"] = jax.vmap(
                lambda k: _init_block(k, cfg, dt))(ek)
            params["enc_final_ln"] = jnp.ones((cfg.d_model,), dt)
        else:  # dense / moe / vlm
            nd = cfg.first_dense_layers
            if nd:
                params["dense_layers"] = jax.vmap(
                    lambda k: _init_block(k, cfg, dt, dense=True))(lk[:nd])
            params["layers"] = jax.vmap(
                lambda k: _init_block(k, cfg, dt))(lk[nd:])
        return params

    def abstract_params(self, seed: int = 0):
        return jax.eval_shape(self.init, jax.random.key(seed))

    @staticmethod
    def _stacks(params) -> list[tuple[str, dict]]:
        """(cache-key prefix, stacked layer params), in depth order."""
        stacks = [("", params["layers"])]
        if "dense_layers" in params:
            stacks.insert(0, ("dense_", params["dense_layers"]))
        return stacks

    def _rope(self, x, positions):
        """Rotary embedding with the config's frequencies: plain, or YaRN
        (frequencies and the cos/sin mscale of DeepSeek-V2)."""
        cfg = self.cfg
        if not cfg.yarn_factor:
            return apply_rope(x, positions, cfg.rope_theta)
        inv_freq = yarn_frequencies(
            x.shape[-1], cfg.rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow)
        mscale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                  / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        return apply_rope(x, positions, cfg.rope_theta, inv_freq, mscale)

    # ====================== attention sub-blocks ====================== #
    def _gqa_qkv(self, h, ap, positions):
        cfg = self.cfg
        b, s, _ = h.shape
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,de->bse", h, ap["wq"]).reshape(
            b, s, cfg.num_heads, hd)
        k = jnp.einsum("bsd,de->bse", h, ap["wk"]).reshape(
            b, s, cfg.num_kv_heads, hd)
        v = jnp.einsum("bsd,de->bse", h, ap["wv"]).reshape(
            b, s, cfg.num_kv_heads, hd)
        q = self._rope(q, positions)
        k = self._rope(k, positions)
        return q, k, v

    def _mla_q(self, h, ap, positions):
        cfg = self.cfg
        b, s, _ = h.shape
        qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
        if "wq" in ap:
            q = jnp.einsum("bsd,de->bse", h, ap["wq"])
        else:
            ql = rms_norm(jnp.einsum("bsd,dr->bsr", h, ap["q_down"]),
                          ap["q_ln"], cfg.norm_eps)
            q = jnp.einsum("bsr,re->bse", ql, ap["q_up"])
        q = q.reshape(b, s, cfg.num_heads, qk_dim)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
        q_rope = self._rope(q_rope, positions)
        return jnp.concatenate([q_nope, q_rope], axis=-1)

    def _mla_latent(self, h, ap, positions):
        """Compressed KV latent: (B,S,kv_lora + rope). Rope pre-applied."""
        cfg = self.cfg
        lat = jnp.einsum("bsd,dr->bsr", h, ap["kv_down"])
        c_kv, k_rope = jnp.split(lat, [cfg.kv_lora_rank], axis=-1)
        c_kv = rms_norm(c_kv, ap["kv_ln"], cfg.norm_eps)
        k_rope = self._rope(k_rope[:, :, None, :], positions)[:, :, 0]
        return jnp.concatenate([c_kv, k_rope], axis=-1)

    def _mla_kv_from_latent(self, latent, ap):
        """Expand cached latent to per-head K (nope+rope) and V."""
        cfg = self.cfg
        b, s, _ = latent.shape
        c_kv, k_rope = jnp.split(latent, [cfg.kv_lora_rank], axis=-1)
        k_nope = jnp.einsum("bsr,re->bse", c_kv, ap["k_up"]).reshape(
            b, s, cfg.num_heads, cfg.qk_nope_dim)
        v = jnp.einsum("bsr,re->bse", c_kv, ap["v_up"]).reshape(
            b, s, cfg.num_heads, cfg.v_head_dim)
        k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, cfg.num_heads, cfg.qk_rope_dim))
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        return k, v

    # ======================= full-sequence blocks ===================== #
    def _attn_full(self, h, lp, positions, causal=True, q_offset=0):
        cfg = self.cfg
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        if cfg.attn_type == "mla":
            q = self._mla_q(hn, lp["attn"], positions)
            latent = self._mla_latent(hn, lp["attn"], positions)
            k, v = self._mla_kv_from_latent(latent, lp["attn"])
            scale = cfg.attn_scale
            o = attn.self_attention(q, k, v, causal=causal,
                                    window=cfg.sliding_window,
                                    q_offset=q_offset, scale=scale,
                                    chunk=cfg.attn_chunk)
            o = o.reshape(*o.shape[:2], -1)
            return h + jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"]), latent
        q, k, v = self._gqa_qkv(hn, lp["attn"], positions)
        o = attn.self_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window, q_offset=q_offset,
                                chunk=cfg.attn_chunk)
        o = o.reshape(*o.shape[:2], -1)
        return h + jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"]), (k, v)

    def _mlp(self, h, lp, dropless=False, valid=None, experts=None):
        """FFN sub-block. Returns (h, extra). In training, `extra` is the
        MoE aux loss (0.0 for a dense FFN); with `dropless` (serving) it
        is the held experts' routing counts (None for a dense FFN),
        `valid` (B,) marks the rows routed at all, and `experts` is
        (the stack's expert matrices, this layer's index) where the
        scan leaves them out of `lp` (`_split_experts`)."""
        cfg = self.cfg
        hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
        mp = lp["mlp"]
        if "router" not in mp:
            return h + swiglu(hn, mp["w_gate"], mp["w_up"],
                              mp["w_down"]), (None if dropless else 0.0)
        if dropless:
            layer = None
            if experts is not None:
                stack, layer = experts
                mp = {**mp, **stack}
            y, extra = moe.moe_ffn_dropless(hn, mp, cfg, valid, layer)
        else:
            y, extra = moe.moe_ffn_sharded(hn, mp, cfg)
        return h + y + moe.shared_ffn(hn, mp), extra

    def _maybe_seq_parallel(self, h):
        """Megatron-SP (§Perf): pin the residual stream to a
        sequence-sharded layout at layer boundaries so remat-saved
        activations are S/16 per device; GSPMD converts the TP
        all-reduces into reduce-scatter + all-gather pairs."""
        cfg = self.cfg
        if not cfg.seq_parallel:
            return h
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty or "model" not in mesh.axis_names:
            return h
        if h.shape[1] % mesh.shape["model"]:
            return h
        bax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bsz = 1
        for a in bax:
            bsz *= mesh.shape[a]
        b_spec = bax if (bax and h.shape[0] % bsz == 0) else None
        return jax.lax.with_sharding_constraint(
            h, jax.sharding.PartitionSpec(b_spec, "model", None))

    def _kv_heads_shardable(self) -> bool:
        """True when the KV-head count divides the model axis — then the
        baseline head-sharded decode attention is already reshard-free and
        the length-sharded path would only waste replicated-q compute."""
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty or "model" not in mesh.axis_names:
            return True
        return self.cfg.num_kv_heads % mesh.shape["model"] == 0

    def _pin_cache(self, arr, kind="kv"):
        """§Perf: pin decode caches to their canonical sharding after the
        token write — GSPMD otherwise flaps between the update's and the
        attention einsum's preferred layouts and falls back to
        'involuntary full rematerialization' (cache replication)."""
        cfg = self.cfg
        if not cfg.pin_cache_sharding:
            return arr
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty or "model" not in mesh.axis_names:
            return arr
        P = jax.sharding.PartitionSpec

        def fits(dim, ax):
            if ax is None:
                return False
            size = 1
            for a in ((ax,) if isinstance(ax, str) else ax):
                if a not in mesh.axis_names:
                    return False
                size *= mesh.shape[a]
            return dim % size == 0

        bax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if kind == "kv":          # (B, S, H, D)
            b, ss, hh, _ = arr.shape
            b_ax = bax if fits(b, bax) else None
            h_ax = "model" if fits(hh, "model") else None
            s_ax = None if h_ax else ("model" if fits(ss, "model") else None)
            spec = P(b_ax, s_ax, h_ax, None)
        else:                     # latent (B, S, R)
            b, ss, _ = arr.shape
            b_ax = bax if fits(b, bax) else None
            spec = P(b_ax, "model" if fits(ss, "model") else None, None)
        return jax.lax.with_sharding_constraint(arr, spec)

    def _block_full(self, h, lp, positions, causal=True):
        h, kv = self._attn_full(h, lp, positions, causal=causal)
        h, aux = self._mlp(h, lp)
        return h, kv, aux

    # ========================= train forward ========================== #
    def loss(self, params: dict, batch: dict) -> jax.Array:
        """Mean next-token CE (+ MoE aux). batch keys:
        tokens (B,S_text) int32, and for vlm/audio `embeds`
        (B, frontend_tokens, D)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.is_encdec:
            logits, aux = self._encdec_forward(params, tokens,
                                               batch["embeds"])
            mask = jnp.ones(tokens.shape, jnp.float32)
            return (cross_entropy_loss(logits[:, :-1], tokens[:, 1:],
                                       mask[:, 1:])
                    + MOE_AUX_COEF * aux)
        h = embed(tokens, params["embed"])
        n_front = 0
        if cfg.frontend != "none" and "embeds" in batch:
            h = jnp.concatenate([batch["embeds"].astype(h.dtype), h], axis=1)
            n_front = batch["embeds"].shape[1]
        s_total = h.shape[1]
        positions = jnp.arange(s_total)[None, :]
        h, aux = self._stack_forward(params, h, positions)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h, params["unembed"], cfg)
        labels_full = jnp.pad(tokens, ((0, 0), (n_front, 0)))
        mask = (jnp.arange(s_total)[None, :] >= n_front).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, labels_full.shape)
        return (cross_entropy_loss(logits[:, :-1], labels_full[:, 1:],
                                   mask[:, 1:]) + MOE_AUX_COEF * aux)

    def _stack_forward(self, params, h, positions):
        cfg = self.cfg
        if cfg.family == "ssm":
            def body(carry, lp):
                return mamba2.mamba2_block(carry, lp, cfg), None
            if cfg.remat:
                body = jax.checkpoint(body)
            h, _ = jax.lax.scan(body, h, params["layers"],
                                unroll=cfg.scan_unroll)
            return h, 0.0
        if cfg.family == "hybrid":
            return self._hybrid_forward(params, h, positions), 0.0

        def body(carry, lp):
            hh, aux = carry
            hh = self._maybe_seq_parallel(hh)
            hh, _, a = self._block_full(hh, lp, positions)
            return (hh, aux + a), None
        if cfg.remat:
            body = jax.checkpoint(body)
        aux = 0.0
        for _, layers in self._stacks(params):
            (h, aux), _ = jax.lax.scan(body, (h, aux), layers,
                                       unroll=cfg.scan_unroll)
        return h, aux

    def _hybrid_forward(self, params, h, positions):
        """Zamba2: scan groups of `hybrid_period` Mamba2 layers, applying
        the single SHARED attention block between groups."""
        cfg = self.cfg
        g, per, rem = (cfg.num_hybrid_groups, cfg.hybrid_period,
                       cfg.hybrid_remainder)
        stacked = params["layers"]
        grouped = jax.tree.map(
            lambda x: x[: g * per].reshape(g, per, *x.shape[1:]), stacked)
        tail = jax.tree.map(lambda x: x[g * per:], stacked)
        shared = params["shared"]

        def group_body(carry, glp):
            def inner(c, lp):
                return mamba2.mamba2_block(c, lp, cfg), None
            if cfg.remat:
                inner = jax.checkpoint(inner)
            c, _ = jax.lax.scan(inner, carry, glp,
                                unroll=cfg.scan_unroll)
            c, _, _ = self._block_full(c, shared, positions)
            return c, None

        if cfg.remat:
            group_body = jax.checkpoint(group_body)
        h, _ = jax.lax.scan(group_body, h, grouped,
                            unroll=cfg.scan_unroll)
        if rem:
            def inner(c, lp):
                return mamba2.mamba2_block(c, lp, cfg), None
            if cfg.remat:
                inner = jax.checkpoint(inner)
            h, _ = jax.lax.scan(inner, h, tail, unroll=cfg.scan_unroll)
        return h

    def _encdec_forward(self, params, tokens, embeds):
        """Seamless-style: bidirectional encoder over frame embeddings,
        causal decoder with cross-attention. Returns (logits, aux)."""
        cfg = self.cfg
        enc_pos = jnp.arange(embeds.shape[1])[None, :]
        henc = embeds.astype(_dtype(cfg))

        def enc_body(c, lp):
            c, _, _ = self._block_full(c, lp, enc_pos, causal=False)
            return c, None
        if cfg.remat:
            enc_body = jax.checkpoint(enc_body)
        henc, _ = jax.lax.scan(enc_body, henc, params["enc_layers"],
                               unroll=cfg.scan_unroll)
        memory = rms_norm(henc, params["enc_final_ln"], cfg.norm_eps)

        h = embed(tokens, params["embed"])
        dec_pos = jnp.arange(tokens.shape[1])[None, :]

        def dec_body(carry, lp):
            hh, aux = carry
            hh, _, a = self._dec_block_full(hh, lp, dec_pos, memory)
            return (hh, aux + a), None
        if cfg.remat:
            dec_body = jax.checkpoint(dec_body)
        (h, aux), _ = jax.lax.scan(dec_body, (h, 0.0), params["layers"],
                                   unroll=cfg.scan_unroll)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        return unembed(h, params["unembed"], cfg), aux

    def _dec_block_full(self, h, lp, positions, memory):
        cfg = self.cfg
        h, kv = self._attn_full(h, lp, positions)
        hn = rms_norm(h, lp["ln_x"], cfg.norm_eps)
        q, km, vm = self._gqa_qkv_mem(hn, lp["xattn"], memory, positions)
        o = attn.cross_attention(q, km, vm)
        o = o.reshape(*o.shape[:2], -1)
        h = h + jnp.einsum("bse,ed->bsd", o, lp["xattn"]["wo"])
        h, aux = self._mlp(h, lp)
        return h, kv, aux

    def _gqa_qkv_mem(self, h, ap, memory, positions):
        """Cross-attention projections: q from decoder, k/v from memory.
        No rope on cross-attention (memory has its own geometry)."""
        cfg = self.cfg
        b, s, _ = h.shape
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,de->bse", h, ap["wq"]).reshape(
            b, s, cfg.num_heads, hd)
        sm = memory.shape[1]
        k = jnp.einsum("bsd,de->bse", memory, ap["wk"]).reshape(
            b, sm, cfg.num_kv_heads, hd)
        v = jnp.einsum("bsd,de->bse", memory, ap["wv"]).reshape(
            b, sm, cfg.num_kv_heads, hd)
        return q, k, v

    # ========================= serving: prefill ======================= #
    def prefill(self, params: dict, tokens: jax.Array,
                embeds: jax.Array | None = None,
                max_len: int | None = None) -> tuple[jax.Array, dict]:
        """Process the full prompt, return (last-token logits, cache).

        Cache arrays are allocated at `max_len` (default: prompt length).
        """
        cfg = self.cfg
        if cfg.family == "ssm":
            return self._prefill_ssm(params, tokens, max_len)
        if cfg.family == "hybrid":
            return self._prefill_hybrid(params, tokens, max_len)
        if cfg.is_encdec:
            return self._prefill_encdec(params, tokens, embeds, max_len)

        h = embed(tokens, params["embed"])
        if cfg.frontend != "none" and embeds is not None:
            h = jnp.concatenate([embeds.astype(h.dtype), h], axis=1)
        b, s, _ = h.shape
        max_len = max_len or s
        positions = jnp.arange(s)[None, :]

        cache, counts = {}, None
        for prefix, layers in self._stacks(params):
            h, kvs, c = self._prefill_stack(layers, h, positions)
            counts = c if c is not None else counts
            cache.update({prefix + k: v for k, v in
                          self._stack_cache(kvs, s, max_len).items()})
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h[:, -1:], params["unembed"], cfg)
        cache["pos"] = jnp.int32(s)
        if counts is not None:
            cache["moe"] = counts
        return logits, cache

    @staticmethod
    def _split_experts(layers):
        """(the stack without its routed expert matrices, a function that
        gives `_mlp` those matrices whole with layer i's index, scanned
        layer indices). The grouped matmul reads a layer's experts out of
        the whole stack; a scan slicing them out would copy them all,
        every layer and step. For a dense stack: (layers, None, None)."""
        mp = layers["mlp"]
        if "router" not in mp:
            return layers, None, None
        stack = {k: mp[k] for k in ("w_gate", "w_up", "w_down")}
        rest = {**layers, "mlp": {k: v for k, v in mp.items()
                                  if k not in stack}}
        n = stack["w_gate"].shape[0]
        return rest, (lambda i: (stack, i)), jnp.arange(n)

    def _prefill_stack(self, layers, h, positions):
        """One stack of blocks over the whole prompt. Returns (h, each
        layer's K/V or latent, the held experts' routing counts or None)."""
        counts = moe.zero_counts(self.cfg) if "router" in layers["mlp"] \
            else None
        layers, experts, index = self._split_experts(layers)

        def body(carry, xs):
            (hh, counts), (lp, i) = carry, xs
            hh, kv = self._attn_full(hh, lp, positions)
            hh, c = self._mlp(hh, lp, dropless=True,
                              experts=experts and experts(i))
            return (hh, moe.add_counts(counts, c)), kv

        (h, counts), kvs = jax.lax.scan(body, (h, counts), (layers, index),
                                        unroll=self.cfg.scan_unroll)
        return h, kvs, counts

    def _stack_cache(self, kvs, s: int, max_len: int) -> dict:
        """A stack's cache entries, padded from `s` tokens to `max_len`."""
        cfg = self.cfg
        pad = max_len - s
        if cfg.attn_type == "mla":
            return {"latent": jnp.pad(kvs, ((0, 0), (0, 0), (0, pad),
                                            (0, 0)))}
        k, v = kvs
        if cfg.swa_ring and cfg.sliding_window:
            k = self._to_ring(k, s)
            v = self._to_ring(v, s)
        else:
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return {"k": k, "v": v}

    def _to_ring(self, k, s):
        """§Perf: convert stacked full-length K/V (L,B,S,H,D) into a
        sliding-window ring buffer (L,B,W,H,D): slot j holds the most
        recent position p with p % W == j (RoPE was applied at write time
        with true positions, so only the mask logic changes)."""
        w = self.cfg.sliding_window
        if s >= w:
            last = k[:, :, s - w:]
            return jnp.roll(last, shift=(s - w) % w, axis=2)
        return jnp.pad(k, ((0, 0), (0, 0), (0, w - s), (0, 0), (0, 0)))

    def _prefill_ssm(self, params, tokens, max_len=None):
        cfg = self.cfg
        h = embed(tokens, params["embed"])

        def body(carry, lp):
            hh = carry
            # run block but also emit final ssm/conv state
            out, cache = self._mamba_block_with_state(hh, lp)
            return out, cache

        h, caches = jax.lax.scan(body, h, params["layers"], unroll=cfg.scan_unroll)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h[:, -1:], params["unembed"], cfg)
        caches["pos"] = jnp.int32(tokens.shape[1])
        return logits, caches

    def _mamba_block_with_state(self, h, lp):
        """mamba2_block variant that returns the decode cache."""
        cfg = self.cfg
        bsz, l, _ = h.shape
        di, n = cfg.d_inner, cfg.ssm_state
        nh, p = cfg.ssm_heads, cfg.ssm_head_dim
        resid = h
        hn = rms_norm(h, lp["ln"], cfg.norm_eps)
        z, xbc, dt = mamba2.mamba2_projections(hn, lp, cfg)
        conv_state = xbc[:, -(cfg.ssm_conv_width - 1):]
        xbc_act = jax.nn.silu(mamba2._causal_conv(xbc, lp["conv"]))
        xin, bg, cg = jnp.split(xbc_act, [di, di + n], axis=-1)
        xh = xin.reshape(bsz, l, nh, p)
        y, h_final = mamba2.ssd_chunked(xh, dt, lp["a_log"], bg, cg,
                                        chunk=min(cfg.ssm_chunk, l))
        y = (y + lp["d_skip"][None, None, :, None] * xh).astype(xh.dtype)
        y = y.reshape(bsz, l, di)
        y = rms_norm(y * jax.nn.silu(z), lp["gate_ln"], cfg.norm_eps)
        out = resid + jnp.einsum("ble,ed->bld", y, lp["w_out"])
        return out, {"conv": conv_state.astype(_dtype(cfg)),
                     "state": h_final}

    def _prefill_hybrid(self, params, tokens, max_len=None):
        cfg = self.cfg
        g, per, rem = (cfg.num_hybrid_groups, cfg.hybrid_period,
                       cfg.hybrid_remainder)
        s = tokens.shape[1]
        max_len = max_len or s
        h = embed(tokens, params["embed"])
        positions = jnp.arange(s)[None, :]
        stacked = params["layers"]
        grouped = jax.tree.map(
            lambda x: x[: g * per].reshape(g, per, *x.shape[1:]), stacked)
        tail = jax.tree.map(lambda x: x[g * per:], stacked)
        shared = params["shared"]
        pad = max_len - s

        def group_body(carry, glp):
            def inner(c, lp):
                return self._mamba_block_with_state(c, lp)
            c, ssm_cache = jax.lax.scan(inner, carry, glp, unroll=cfg.scan_unroll)
            c, (k, v), _ = self._block_full(c, shared, positions)
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return c, (ssm_cache, {"k": k, "v": v})

        h, (ssm_caches, attn_caches) = jax.lax.scan(group_body, h, grouped, unroll=cfg.scan_unroll)
        tail_cache = None
        if rem:
            def inner(c, lp):
                return self._mamba_block_with_state(c, lp)
            h, tail_cache = jax.lax.scan(inner, h, tail, unroll=cfg.scan_unroll)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h[:, -1:], params["unembed"], cfg)
        cache = {"ssm": ssm_caches, "attn": attn_caches,
                 "tail": tail_cache, "pos": jnp.int32(s)}
        return logits, cache

    def _prefill_encdec(self, params, tokens, embeds, max_len=None):
        """Encode memory once; prefill decoder self+cross caches."""
        cfg = self.cfg
        enc_pos = jnp.arange(embeds.shape[1])[None, :]
        henc = embeds.astype(_dtype(cfg))

        def enc_body(c, lp):
            c, _, _ = self._block_full(c, lp, enc_pos, causal=False)
            return c, None
        henc, _ = jax.lax.scan(enc_body, henc, params["enc_layers"], unroll=cfg.scan_unroll)
        memory = rms_norm(henc, params["enc_final_ln"], cfg.norm_eps)

        s = tokens.shape[1]
        max_len = max_len or s
        pad = max_len - s
        h = embed(tokens, params["embed"])
        dec_pos = jnp.arange(s)[None, :]

        def dec_body(carry, lp):
            hh, aux = carry
            hn = rms_norm(hh, lp["ln1"], cfg.norm_eps)
            q, k, v = self._gqa_qkv(hn, lp["attn"], dec_pos)
            o = attn.self_attention(q, k, v, causal=True)
            o = o.reshape(*o.shape[:2], -1)
            hh = hh + jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"])
            hn = rms_norm(hh, lp["ln_x"], cfg.norm_eps)
            qx, km, vm = self._gqa_qkv_mem(hn, lp["xattn"], memory, dec_pos)
            ox = attn.cross_attention(qx, km, vm)
            ox = ox.reshape(*ox.shape[:2], -1)
            hh = hh + jnp.einsum("bse,ed->bsd", ox, lp["xattn"]["wo"])
            hh, a = self._mlp(hh, lp)
            kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return (hh, aux + a), (kp, vp, km, vm)

        (h, _), (ks, vs, kms, vms) = jax.lax.scan(dec_body, (h, 0.0),
                                                  params["layers"], unroll=cfg.scan_unroll)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h[:, -1:], params["unembed"], cfg)
        cache = {"k": ks, "v": vs, "xk": kms, "xv": vms,
                 "pos": jnp.int32(s)}
        return logits, cache

    # ========================= serving: decode ======================== #
    def decode_step(self, params: dict, cache: dict, token: jax.Array,
                    active: jax.Array | None = None
                    ) -> tuple[jax.Array, dict]:
        """One decode step. token: (B, 1) int32; `active` (B,) marks the
        rows whose tokens the MoE layers route (all when None). Returns
        (logits, cache)."""
        cfg = self.cfg
        cache = dict(cache)
        cache["pos"] = jnp.asarray(cache["pos"], jnp.int32)
        if cfg.family == "ssm":
            return self._decode_ssm(params, cache, token)
        if cfg.family == "hybrid":
            return self._decode_hybrid(params, cache, token)
        if cfg.is_encdec:
            return self._decode_encdec(params, cache, token)

        pos = cache["pos"]
        h = embed(token, params["embed"])
        positions = (jnp.full((1, 1), pos, jnp.int32) if pos.ndim == 0
                     else pos[:, None])

        new_cache, counts = {}, None
        for prefix, layers in self._stacks(params):
            h, entries, c = self._decode_stack(
                layers, {k: cache[prefix + k] for k in
                         (("latent",) if cfg.attn_type == "mla"
                          else ("k", "v"))},
                h, pos, positions, active)
            counts = c if c is not None else counts
            new_cache.update({prefix + k: v for k, v in entries.items()})
        new_cache["pos"] = pos + 1
        if counts is not None:
            new_cache["moe"] = counts
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        return unembed(h, params["unembed"], cfg), new_cache

    def _decode_stack(self, layers, cache, h, pos, positions, valid):
        """One stack of blocks for one token per row. Returns (h, the
        stack's new cache entries, routing counts or None)."""
        cfg = self.cfg
        counts = moe.zero_counts(cfg) if "router" in layers["mlp"] else None
        layers, experts, index = self._split_experts(layers)
        if cfg.attn_type == "mla":
            def body(carry, xs):
                lp, lat, i = xs
                hh, counts = carry
                hh, lat, c = self._mla_decode_block(
                    hh, lp, lat, pos, positions, valid,
                    experts and experts(i))
                return (hh, moe.add_counts(counts, c)), lat
            (h, counts), latents = jax.lax.scan(
                body, (h, counts), (layers, cache["latent"], index),
                unroll=cfg.scan_unroll)
            return h, {"latent": latents}, counts

        def body(carry, xs):
            lp, ck, cv, i = xs
            hh, counts = carry
            hh, nk, nv, c = self._gqa_decode_block(
                hh, lp, ck, cv, pos, positions, valid,
                experts and experts(i))
            return (hh, moe.add_counts(counts, c)), (nk, nv)
        (h, counts), (nks, nvs) = jax.lax.scan(
            body, (h, counts), (layers, cache["k"], cache["v"], index),
            unroll=cfg.scan_unroll)
        return h, {"k": nks, "v": nvs}, counts

    def _gqa_decode_block(self, h, lp, ck, cv, pos, positions, valid=None,
                          experts=None):
        cfg = self.cfg
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = self._gqa_qkv(hn, lp["attn"], positions)
        ring = cfg.swa_ring and cfg.sliding_window
        if ring:
            w = cfg.sliding_window
            ck, cv = attn.cache_update(ck, cv, k, v, pos % w)
            pos_eff = jnp.minimum(pos + 1, w)
            window = 0   # the ring holds exactly the window
        else:
            ck, cv = attn.cache_update(ck, cv, k, v, pos)
            pos_eff = pos + 1
            window = cfg.sliding_window
        if cfg.pin_cache_sharding and not self._kv_heads_shardable():
            ck, cv = self._pin_cache(ck), self._pin_cache(cv)
            o = attn.decode_attention_length_sharded(
                q, ck, cv, pos_eff, window=window)
        else:
            o = attn.decode_attention(q, ck, cv, pos_eff, window=window)
        o = o.reshape(*o.shape[:2], -1)
        h = h + jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"])
        h, counts = self._mlp(h, lp, dropless=True, valid=valid,
                              experts=experts)
        return h, ck, cv, counts

    def _mla_decode_block(self, h, lp, latent_cache, pos, positions,
                          valid=None, experts=None):
        """MLA decode: append this token's latent and attend against the
        latent cache in the absorbed form (`_mla_absorbed_attention`)."""
        cfg = self.cfg
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q = self._mla_q(hn, lp["attn"], positions)
        lat_new = self._mla_latent(hn, lp["attn"], positions)
        lat_new = lat_new.astype(latent_cache.dtype)
        if pos.ndim == 0:
            latent_cache = jax.lax.dynamic_update_slice_in_dim(
                latent_cache, lat_new, pos, axis=1)
        else:
            latent_cache = latent_cache.at[
                jnp.arange(latent_cache.shape[0]), pos].set(lat_new[:, 0])
        latent_cache = self._pin_cache(latent_cache, kind="latent")
        o = self._mla_absorbed_attention(q, latent_cache, lp["attn"], pos,
                                         cfg.attn_scale)
        o = o.reshape(*o.shape[:2], -1)
        h = h + jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"])
        h, counts = self._mlp(h, lp, dropless=True, valid=valid,
                              experts=experts)
        return h, latent_cache, counts

    def _mla_absorbed_attention(self, q, latent_cache, ap, pos, scale):
        """Decode attention against the compressed latent cache
        (DeepSeek-V2 §2.1): k_up is folded into the query and v_up into
        the output, so per-head K and V of shape (B, S, H, d) are never
        built. One query per row makes expanding the cache cost
        O(S·kv_lora·H·(nope+v)) a token, against O(S·H·(kv_lora+rope))
        here.

        Both contractions read the cache as stored: the scores contract
        the query side, [q_nope·k_up, q_rope], with the whole latent row
        [c_kv, k_rope], and the output weighs its first kv_lora_rank
        columns. The query side and the probabilities are rounded to
        the cache's dtype, as the materialised K and V and the
        probabilities of `attention.attention` are; products accumulate
        and the softmax runs in float32."""
        cfg = self.cfg
        r, heads = cfg.kv_lora_rank, cfg.num_heads
        dt, f32 = latent_cache.dtype, jnp.float32
        q_nope, q_rope = jnp.split(q[:, 0], [cfg.qk_nope_dim], axis=-1)
        k_up = ap["k_up"].reshape(r, heads, cfg.qk_nope_dim)
        # q_eff[b,h,r] = sum_d q_nope[b,h,d] * k_up[r,h,d]
        q_eff = jnp.einsum("bhd,rhd->bhr", q_nope, k_up,
                           preferred_element_type=f32)
        q_lat = jnp.concatenate([q_eff.astype(dt), q_rope.astype(dt)],
                                axis=-1)
        scores = jnp.einsum("bhr,bsr->bhs", q_lat, latent_cache,
                            preferred_element_type=f32) * scale
        idx = jnp.arange(latent_cache.shape[1])[None, None, :]
        p = pos if pos.ndim == 0 else pos[:, None, None]
        scores = jnp.where(idx <= p, scores, attn.NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        out_lat = jnp.einsum("bhs,bsr->bhr", probs, latent_cache[..., :r],
                             preferred_element_type=f32)
        v_up = ap["v_up"].reshape(r, heads, cfg.v_head_dim)
        o = jnp.einsum("bhr,rhd->bhd", out_lat.astype(dt), v_up,
                       preferred_element_type=f32)
        return o[:, None].astype(q.dtype)

    def _decode_ssm(self, params, cache, token):
        cfg = self.cfg
        h = embed(token, params["embed"])

        def body(carry, xs):
            lp, conv, state = xs
            hh = carry
            hh, nc = mamba2.mamba2_block_decode(
                hh, lp, {"conv": conv, "state": state}, cfg)
            return hh, (nc["conv"], nc["state"])
        h, (convs, states) = jax.lax.scan(
            body, h, (params["layers"], cache["conv"], cache["state"]), unroll=cfg.scan_unroll)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h, params["unembed"], cfg)
        return logits, {"conv": convs, "state": states,
                        "pos": cache["pos"] + 1}

    def _decode_hybrid(self, params, cache, token):
        cfg = self.cfg
        g, per, rem = (cfg.num_hybrid_groups, cfg.hybrid_period,
                       cfg.hybrid_remainder)
        pos = cache["pos"]
        positions = (jnp.full((1, 1), pos, jnp.int32) if pos.ndim == 0
                     else pos[:, None])
        h = embed(token, params["embed"])
        stacked = params["layers"]
        grouped = jax.tree.map(
            lambda x: x[: g * per].reshape(g, per, *x.shape[1:]), stacked)
        tail = jax.tree.map(lambda x: x[g * per:], stacked)
        shared = params["shared"]

        def group_body(carry, xs):
            glp, ssm_c, attn_c = xs
            c = carry

            def inner(cc, ys):
                lp, conv, state = ys
                cc, nc = mamba2.mamba2_block_decode(
                    cc, lp, {"conv": conv, "state": state}, cfg)
                return cc, (nc["conv"], nc["state"])
            c, (convs, states) = jax.lax.scan(
                inner, c, (glp, ssm_c["conv"], ssm_c["state"]), unroll=cfg.scan_unroll)
            c, nk, nv, _ = self._gqa_decode_block(
                c, shared, attn_c["k"], attn_c["v"], pos, positions)
            return c, ({"conv": convs, "state": states},
                       {"k": nk, "v": nv})

        h, (new_ssm, new_attn) = jax.lax.scan(
            group_body, h, (grouped, cache["ssm"], cache["attn"]), unroll=cfg.scan_unroll)
        new_tail = None
        if rem:
            def inner(cc, ys):
                lp, conv, state = ys
                cc, nc = mamba2.mamba2_block_decode(
                    cc, lp, {"conv": conv, "state": state}, cfg)
                return cc, (nc["conv"], nc["state"])
            h, (tconvs, tstates) = jax.lax.scan(
                inner, h, (tail, cache["tail"]["conv"],
                           cache["tail"]["state"]), unroll=cfg.scan_unroll)
            new_tail = {"conv": tconvs, "state": tstates}
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h, params["unembed"], cfg)
        return logits, {"ssm": new_ssm, "attn": new_attn, "tail": new_tail,
                        "pos": pos + 1}

    def _decode_encdec(self, params, cache, token):
        cfg = self.cfg
        pos = cache["pos"]
        positions = (jnp.full((1, 1), pos, jnp.int32) if pos.ndim == 0
                     else pos[:, None])
        h = embed(token, params["embed"])

        def body(carry, xs):
            lp, ck, cv, km, vm = xs
            hh = carry
            hn = rms_norm(hh, lp["ln1"], cfg.norm_eps)
            q, k, v = self._gqa_qkv(hn, lp["attn"], positions)
            ck, cv = attn.cache_update(ck, cv, k, v, pos)
            if cfg.pin_cache_sharding and not self._kv_heads_shardable():
                ck, cv = self._pin_cache(ck), self._pin_cache(cv)
                o = attn.decode_attention_length_sharded(q, ck, cv, pos + 1)
            else:
                o = attn.decode_attention(q, ck, cv, pos + 1)
            o = o.reshape(*o.shape[:2], -1)
            hh = hh + jnp.einsum("bse,ed->bsd", o, lp["attn"]["wo"])
            hn = rms_norm(hh, lp["ln_x"], cfg.norm_eps)
            hd = cfg.resolved_head_dim
            b = hn.shape[0]
            qx = jnp.einsum("bsd,de->bse", hn, lp["xattn"]["wq"]).reshape(
                b, 1, cfg.num_heads, hd)
            ox = attn.cross_attention(qx, km, vm)
            ox = ox.reshape(*ox.shape[:2], -1)
            hh = hh + jnp.einsum("bse,ed->bsd", ox, lp["xattn"]["wo"])
            hh, _ = self._mlp(hh, lp)
            return hh, (ck, cv)

        h, (nks, nvs) = jax.lax.scan(
            body, h, (params["layers"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]), unroll=cfg.scan_unroll)
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = unembed(h, params["unembed"], cfg)
        return logits, {"k": nks, "v": nvs, "xk": cache["xk"],
                        "xv": cache["xv"], "pos": pos + 1}
