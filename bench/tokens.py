"""Token traffic for the training cells, drawn on the device from a seed.

A traffic file fixes the shape of a round (clients x rows per client x
sequence length) and the token statistics; this one generator reads them.
Tokens are Zipf-distributed ranks, and with probability ``copy_prob`` a
token is ``(previous * copy_mul + copy_add) % vocab`` instead, so the
streams hold a signal a language model can learn (the same recipe as
``repro.data.lm_token_batches``, kept here so the yardstick does not move
with the program).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def round_tokens(traffic: dict) -> int:
    """Tokens of all clients in one round."""
    return traffic["clients"] * traffic["rows_per_client"] * traffic["seq_len"]


def pool(key: jax.Array, traffic: dict, vocab: int, rounds: int) -> jax.Array:
    """``rounds`` batches of shape (clients * rows_per_client, seq_len),
    int32, stacked on a leading axis; client k owns rows
    [k * rows_per_client, (k + 1) * rows_per_client) of each batch."""
    t = traffic["tokens"]
    shape = (rounds, traffic["clients"] * traffic["rows_per_client"],
             traffic["seq_len"])
    k_rank, k_coin = jax.random.split(key)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    probs = ranks ** (-t["zipf_a"])
    base = jax.random.choice(k_rank, vocab, shape, p=probs / probs.sum())
    copied = (jnp.roll(base, 1, axis=-1) * t["copy_mul"] + t["copy_add"]) % vocab
    coin = jax.random.bernoulli(k_coin, t["copy_prob"], shape)
    return jnp.where(coin, copied, base).astype(jnp.int32)
