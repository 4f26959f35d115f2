"""Seeded random weights of an MLA decoder, made by the benchmark.

Every matrix is drawn from N(0, 0.02^2) and every RMSNorm gain from
1 + N(0, 0.1^2), then rounded to bfloat16, the type the program serves
in. Layer `l`'s weights come from `fold_in(key, l)`, with `key` =
`jax.random.key(seed)`, so the program's stacked copy (drawn in one
jitted call on the device) and the reference's layer-by-layer copy hold
the same numbers, and the reference never reads what the program holds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02
NORM_STD = 0.1
EMBED_KEY = 1_000_003        # fold-in index of the embedding tables


def layer_shapes(m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return {
        "ln1": (d,),
        "q_down": (d, m["q_lora_rank"]),
        "q_ln": (m["q_lora_rank"],),
        "q_up": (m["q_lora_rank"], h * qk),
        "kv_down": (d, m["kv_lora_rank"] + m["qk_rope_head_dim"]),
        "kv_ln": (m["kv_lora_rank"],),
        "k_up": (m["kv_lora_rank"], h * m["qk_nope_head_dim"]),
        "v_up": (m["kv_lora_rank"], h * m["v_head_dim"]),
        "wo": (h * m["v_head_dim"], d),
        "ln2": (d,),
        "w_gate": (d, m["intermediate_size"]),
        "w_up": (d, m["intermediate_size"]),
        "w_down": (m["intermediate_size"], d),
    }


def _draw(key, name: str, shape) -> jax.Array:
    """float32 values that are exactly bfloat16 numbers."""
    z = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + NORM_STD * z if len(shape) == 1 else STD * z
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def layer(key, index, m: dict) -> dict:
    """Layer `index`'s weights as float32 (traceable in `key` and
    `index`)."""
    key = jax.random.fold_in(key, index)
    shapes = layer_shapes(m)
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, name, shape)
            for k, (name, shape) in zip(keys, shapes.items())}


def tables(key, m: dict, padded_vocab: int) -> dict:
    """Input embedding, output embedding (padded_vocab, d) and the final
    RMSNorm gain."""
    key = jax.random.fold_in(key, EMBED_KEY)
    k1, k2, k3 = jax.random.split(key, 3)
    d = m["hidden_size"]
    return {"embed": _draw(k1, "embed", (padded_vocab, d)),
            "unembed": _draw(k2, "unembed", (padded_vocab, d)),
            "final_ln": _draw(k3, "final_ln", (d,))}


def program_params(key, m: dict, padded_vocab: int, abstract):
    """The program's parameter tree (`repro.models.Model` layout: layers
    stacked on a leading axis), drawn on the device in one jitted call
    and cast to bfloat16. `abstract` is the program's own
    `abstract_params()`; the tree built here must match it leaf for
    leaf."""
    n = m["num_hidden_layers"]

    def build(key):
        stacked = jax.vmap(lambda i: layer(key, i, m))(jnp.arange(n))
        t = tables(key, m, padded_vocab)
        cast = lambda x: x.astype(jnp.bfloat16)     # noqa: E731
        return {
            "embed": cast(t["embed"]), "unembed": cast(t["unembed"]),
            "final_ln": cast(t["final_ln"]),
            "layers": {
                "ln1": cast(stacked["ln1"]), "ln2": cast(stacked["ln2"]),
                "attn": {k: cast(stacked[k]) for k in (
                    "q_down", "q_ln", "q_up", "kv_down", "kv_ln", "k_up",
                    "v_up", "wo")},
                "mlp": {k: cast(stacked[k]) for k in (
                    "w_gate", "w_up", "w_down")},
            },
        }

    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       jax.eval_shape(build, key))
    if want != got:
        raise ValueError(f"the program's parameter layout changed: "
                         f"expected {want}, built {got}")
    return jax.jit(build)(key)
