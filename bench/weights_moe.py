"""Seeded random weights of a DeepSeek-V2-style decoder, made by the
benchmark: MLA attention with queries straight from x, leading dense
layers, then MoE layers with a router over every routed expert, this
chip's share of the routed experts, and shared experts.

As in `bench/weights.py`, every matrix is drawn from N(0, 0.02^2) and
every RMSNorm gain from 1 + N(0, 0.1^2), then rounded to bfloat16.
Layer `l`'s weights come from `fold_in(key, l)`, and routed expert `e`
of that layer from `fold_in(fold_in(key, l), EXPERT_KEY + e)` by its
index among all routed experts, so that every share of the experts
draws the same numbers for the experts it holds, the program's stacked
copy and the reference's layer-by-layer copy agree, and the reference
never reads what the program holds.

`m` is the configuration's published model config (Hugging Face key
names, with `n_routed_experts` the experts held here) plus
`router_experts` (the router's width) and `expert_offset` (the first
held expert's index).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import tables

STD = 0.02
NORM_STD = 0.1
EXPERT_KEY = 1_000


def _draw(key, shape) -> jax.Array:
    """float32 values that are exactly bfloat16 numbers."""
    z = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + NORM_STD * z if len(shape) == 1 else STD * z
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def attn_shapes(m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r = m["kv_lora_rank"]
    return {
        "ln1": (d,),
        "wq": (d, h * (nope + rope)),
        "kv_down": (d, r + rope),
        "kv_ln": (r,),
        "k_up": (r, h * nope),
        "v_up": (r, h * m["v_head_dim"]),
        "wo": (h * m["v_head_dim"], d),
        "ln2": (d,),
    }


def ffn_shapes(m: dict, dense: bool) -> dict:
    """The layer's FFN outside the routed experts: a dense SwiGLU of
    `intermediate_size`, or the router and the shared experts."""
    d = m["hidden_size"]
    if dense:
        f = m["intermediate_size"]
        return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    fs = m["n_shared_experts"] * m["moe_intermediate_size"]
    return {"router": (d, m["router_experts"]), "shared_gate": (d, fs),
            "shared_up": (d, fs), "shared_down": (fs, d)}


def layer(key, index, m: dict, dense: bool) -> dict:
    """Layer `index`'s weights outside the routed experts, float32
    (traceable in `key` and `index`)."""
    key = jax.random.fold_in(key, index)
    shapes = {**attn_shapes(m), **ffn_shapes(m, dense)}
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, shape)
            for k, (name, shape) in zip(keys, shapes.items())}


def expert(key, index, e, m: dict) -> dict:
    """Routed expert `e` (its index among all routed experts) of layer
    `index`: w_gate, w_up (d, f), w_down (f, d), float32."""
    key = jax.random.fold_in(jax.random.fold_in(key, index), EXPERT_KEY + e)
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": _draw(kg, (d, f)), "w_up": _draw(ku, (d, f)),
            "w_down": _draw(kd, (f, d))}


def held_experts(key, index, m: dict) -> dict:
    """The held experts of layer `index`, stacked on a leading axis."""
    ids = m["expert_offset"] + jnp.arange(m["n_routed_experts"])
    return jax.vmap(lambda e: expert(key, index, e, m))(ids)


def program_params(key, m: dict, padded_vocab: int, abstract):
    """The program's parameter tree (`repro.models.Model` layout: the
    leading dense layers and the MoE layers each stacked on a leading
    axis), drawn on the device layer by layer (`lax.map`, so no float32
    copy of a whole stack exists) and cast to bfloat16; the router stays
    float32, as the program keeps it. `abstract` is the program's own
    `abstract_params()`; the tree built here must match it leaf for
    leaf."""
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    bf16 = jnp.bfloat16

    def common(w):
        return {"ln1": w["ln1"].astype(bf16), "ln2": w["ln2"].astype(bf16),
                "attn": {k: w[k].astype(bf16) for k in (
                    "wq", "kv_down", "kv_ln", "k_up", "v_up", "wo")}}

    def build(key):
        def dense_layer(i):
            w = layer(key, i, m, dense=True)
            return {**common(w), "mlp": {
                k: w[k].astype(bf16) for k in ("w_gate", "w_up", "w_down")}}

        def moe_layer(i):
            w = layer(key, i, m, dense=False)
            ex = held_experts(key, i, m)
            return {**common(w), "mlp": {
                "router": w["router"],
                **{k: v.astype(bf16) for k, v in ex.items()},
                "shared": {"w_gate": w["shared_gate"].astype(bf16),
                           "w_up": w["shared_up"].astype(bf16),
                           "w_down": w["shared_down"].astype(bf16)}}}

        t = tables(key, m, padded_vocab)
        params = {"embed": t["embed"].astype(bf16),
                  "unembed": t["unembed"].astype(bf16),
                  "final_ln": t["final_ln"].astype(bf16),
                  "layers": jax.lax.map(moe_layer,
                                        n_dense + jnp.arange(n_moe))}
        if n_dense:
            params["dense_layers"] = jax.lax.map(dense_layer,
                                                 jnp.arange(n_dense))
        return params

    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       jax.eval_shape(build, key))
    if want != got:
        raise ValueError(f"the program's parameter layout changed: "
                         f"expected {want}, built {got}")
    return jax.jit(build)(key)
