"""Random weights drawn from the run's seed, on the device.

Every leaf is named by its path in the parameter tree (``blocks/attn/wq``)
and drawn from a key that depends on the seed, the name and, for a layer
leaf, the layer index.  The served model gets all leaves from one jitted
call; the reference draws one layer at a time with the same keys, so both
see the same values without the reference taking any array the program
made.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of one decoder configuration, as the benchmark reads them
    from its configuration file."""
    name: str
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    act: str            # "silu" (gated) or "relu2" (squared ReLU, ungated)
    tied: bool
    qk_norm: bool
    rope_theta: float
    eps: float
    embed_std: float
    norm_std: float
    qk_gain: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256


def model_from_config(conf: dict) -> Model:
    init = conf.get("init", {})
    return Model(
        name=conf["name"], d=conf["hidden_size"],
        ff=conf["intermediate_size"], layers=conf["num_hidden_layers"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab=conf["vocab_size"], act=conf["hidden_act"],
        tied=conf["tie_word_embeddings"],
        qk_norm=conf["model_type"] == "qwen3",
        rope_theta=float(conf.get("rope_theta", 10000.0)),
        eps=float(conf.get("rms_norm_eps", 1e-6)),
        embed_std=float(init.get("embed_std", 1.0)),
        norm_std=float(init.get("norm_std", 0.0)),
        qk_gain=float(init.get("qk_gain", 1.0)))


def layer_leaves(m: Model
                 ) -> dict[str, tuple[tuple[int, ...], float, float]]:
    """name -> (shape of one layer's leaf, std, mean).  Norm leaves are
    zero-centred gain offsets (gain = 1 + w).  ``qk_gain`` scales the
    attention scores: through the query/key norm gains where the model
    normalizes them, else through the query projection."""
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    q_std = (1 if m.qk_norm else m.qk_gain) / math.sqrt(m.d)
    out = {
        "ln1": ((m.d,), m.norm_std, 0.0),
        "ln2": ((m.d,), m.norm_std, 0.0),
        "attn/wq": ((m.d, qd), q_std, 0.0),
        "attn/wk": ((m.d, kvd), 1 / math.sqrt(m.d), 0.0),
        "attn/wv": ((m.d, kvd), 1 / math.sqrt(m.d), 0.0),
        "attn/wo": ((qd, m.d), 1 / math.sqrt(qd), 0.0),
        "mlp/up": ((m.d, m.ff), 1 / math.sqrt(m.d), 0.0),
        "mlp/down": ((m.ff, m.d), 1 / math.sqrt(m.ff), 0.0),
    }
    if m.act == "silu":
        out["mlp/gate"] = ((m.d, m.ff), 1 / math.sqrt(m.d), 0.0)
    if m.qk_norm:
        g = math.sqrt(m.qk_gain) - 1
        out["attn/q_norm"] = ((m.head_dim,), m.norm_std, g)
        out["attn/k_norm"] = ((m.head_dim,), m.norm_std, g)
    return out


def top_leaves(m: Model) -> dict[str, tuple[tuple[int, ...], float]]:
    out = {"embed": ((m.padded_vocab, m.d), m.embed_std / math.sqrt(m.d)),
           "final_norm": ((m.d,), m.norm_std)}
    if not m.tied:
        out["lm_head"] = ((m.d, m.padded_vocab), 1 / math.sqrt(m.d))
    return out


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative whole number (more than 32
    bits allowed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, shape, std, dtype, mean=0.0):
    return (jax.random.normal(key, shape, jnp.float32) * std + mean
            ).astype(dtype)


def _layer(key, m: Model, layer, dtype) -> dict[str, jax.Array]:
    return {name: _draw(jax.random.fold_in(_leaf_key(key, name), layer),
                        shape, std, dtype, mean)
            for name, (shape, std, mean) in layer_leaves(m).items()}


def _nest(flat: dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for name, x in flat.items():
        *parents, leaf = name.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


@functools.lru_cache(maxsize=None)
def _builders(m: Model, dtype):
    def top(key):
        return {name: _draw(_leaf_key(key, name), shape, std, dtype)
                for name, (shape, std) in top_leaves(m).items()}

    def whole(key):
        blocks = jax.vmap(lambda l: _layer(key, m, l, dtype))(
            jnp.arange(m.layers))
        return {**top(key), "blocks": _nest(blocks)}

    return (jax.jit(whole), jax.jit(lambda key, l: _layer(key, m, l, dtype)),
            jax.jit(top))


def make_params(m: Model, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the program's layout (layer leaves
    stacked on a leading axis), in one jitted call on the default device."""
    return _builders(m, jnp.dtype(dtype))[0](seed_key(seed))


def layer_weights(m: Model, seed: int, layer: int,
                  dtype=jnp.bfloat16) -> dict[str, jax.Array]:
    """One layer's leaves by name, equal to ``make_params``'s slice."""
    return _builders(m, jnp.dtype(dtype))[1](
        seed_key(seed), jnp.asarray(layer, jnp.int32))


def top_weights(m: Model, seed: int, dtype=jnp.bfloat16
                ) -> dict[str, jax.Array]:
    """Embedding, final norm and (untied) head by name."""
    return _builders(m, jnp.dtype(dtype))[2](seed_key(seed))
