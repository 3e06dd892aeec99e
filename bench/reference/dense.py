"""Plain reference of the dense decoder block (Qwen2, Phi-3): its weights,
and its next-token loss in straightforward ``jax.numpy``.

The block, from the published modeling code: RMSNorm; q, k, v projections
(with biases where ``program.qkv_bias`` says so); rotary embedding on the
two halves of each head; grouped-query causal softmax attention; the
output projection; RMSNorm; the SwiGLU MLP ``down(silu(gate(x)) * up(x))``;
a final RMSNorm and the head (the embedding transposed when tied).  The
loss is the mean cross-entropy of each next token.

Every product goes through the ``matmul`` / ``einsum`` pair the caller
passes, so one function gives both the float32 reference at ``highest``
precision and the lower-precision control.  Nothing here imports the
program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MATRICES = ("embed", "lm_head", "wq", "wk", "wv", "wo",
            "w_gate", "w_up", "w_down")


def dims(conf: dict) -> dict:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    return {"D": D, "H": H, "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or D // H,
            "F": conf["intermediate_size"], "V": conf["vocab_size"],
            "L": conf["num_hidden_layers"]}


def layout(conf: dict) -> dict:
    """Leaf shapes of the weights, in the tree the trainer stores: per-layer
    leaves stacked on a leading layer axis under ``blocks``."""
    d = dims(conf)
    D, L, F, V = d["D"], d["L"], d["F"], d["V"]
    Q, KV = d["H"] * d["hd"], d["KV"] * d["hd"]
    blocks = {"ln1": (L, D), "ln2": (L, D), "wq": (L, D, Q), "wk": (L, D, KV),
              "wv": (L, D, KV), "wo": (L, Q, D), "w_gate": (L, D, F),
              "w_up": (L, D, F), "w_down": (L, F, D)}
    if conf["program"].get("qkv_bias"):
        blocks.update(bq=(L, Q), bk=(L, KV), bv=(L, KV))
    tree = {"embed": (V, D), "ln_f": (D,), "blocks": blocks}
    if not conf["tie_word_embeddings"]:
        tree["lm_head"] = (D, V)
    return tree


def init(key: jax.Array, conf: dict) -> dict:
    """Weights from ``key`` in the configuration's dtype: matrices
    N(0, initializer_range^2), norm scales 1, biases 0.  Leaf i of the
    flattened (sorted) tree draws from ``fold_in(key, i)``.  Call it under
    ``jax.jit`` so the weights are made on the device."""
    dtype = jnp.dtype(conf["torch_dtype"])
    shapes, treedef = jax.tree.flatten(
        layout(conf), is_leaf=lambda x: isinstance(x, tuple))
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(
                 layout(conf), is_leaf=lambda x: isinstance(x, tuple))[0]]
    leaves = []
    for i, (name, shape) in enumerate(zip(names, shapes)):
        leaf = name.split("'")[-2]
        if leaf.startswith("ln"):
            leaves.append(jnp.ones(shape, dtype))
        elif leaf.startswith("b"):
            leaves.append(jnp.zeros(shape, dtype))
        elif leaf in MATRICES:
            leaves.append((conf["initializer_range"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype))
        else:
            raise ValueError(f"no initializer for weight {name}")
    return jax.tree.unflatten(treedef, leaves)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (R, S, heads, hd); rotates the pair (i, i + hd/2) of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params: dict, tokens: jax.Array, conf: dict, matmul, einsum):
    """Mean next-token cross-entropy of ``tokens`` (R, S) under float32
    ``params``; ``matmul(a, b)`` and ``einsum(spec, a, b)`` do every
    product."""
    d = dims(conf)
    H, KV, hd = d["H"], d["KV"], d["hd"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    R, S = tokens.shape
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q, k, v = (matmul(h, lp[w]) for w in ("wq", "wk", "wv"))
        if "bq" in lp:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = _rope(q.reshape(R, S, H, hd), theta)
        k = jnp.repeat(_rope(k.reshape(R, S, KV, hd), theta), H // KV, axis=2)
        v = jnp.repeat(v.reshape(R, S, KV, hd), H // KV, axis=2)
        s = einsum("rqhd,rkhd->rhqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = einsum("rhqk,rkhd->rqhd", p, v).reshape(R, S, H * hd)
        x = x + matmul(o, lp["wo"])
        h = _rms(x, lp["ln2"], eps)
        y = matmul(jax.nn.silu(matmul(h, lp["w_gate"])) * matmul(h, lp["w_up"]),
                   lp["w_down"])
        return x + y, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = _rms(x, params["ln_f"], eps)
    head = params["embed"].T if conf["tie_word_embeddings"] else params["lm_head"]
    logits = matmul(x, head)[:, :-1]
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - target)
