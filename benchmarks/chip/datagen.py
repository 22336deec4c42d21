"""Client token streams for the benchmark, made on the device from the seed.

A copy of the program's ``repro.data.synthetic.HeteroLMDataset`` (per-client
unigram tables mixed from a shared and a client-unique draw, first-order
structure through a roll of the table by the previous token), kept here so
that the traffic cannot change when the program does. The program draws one
round at a time, eagerly on the host; here a whole segment of rounds is drawn
in one jitted call. ``tests/test_datagen.py`` pins the tokens against the
program's generator at the same seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def client_logits(vocab: int, n_clients: int, heterogeneity: float,
                  seed: int) -> jax.Array:
    """[clients, vocab] per-client unigram logit tables."""
    base = jax.random.normal(jax.random.key(seed), (vocab,))
    uniq = jax.random.normal(jax.random.key(seed + 1), (n_clients, vocab))
    h = heterogeneity
    return (1.0 - h) * base[None, :] + h * 2.0 * uniq


def sample_round(logits: jax.Array, round_index, *, tau: int, batch: int,
                 seq_len: int, seed: int) -> jax.Array:
    """Tokens [tau, clients, batch, seq] of one round."""
    n_clients = logits.shape[0]
    key = jax.random.fold_in(jax.random.key(seed + 2), round_index)

    def sample_client(ckey, clogits):
        ks = jax.random.split(ckey, tau * batch)

        def sample_seq(k):
            def step(tok, kk):
                nxt = jax.random.categorical(kk, jnp.roll(clogits, tok)
                                             + clogits)
                return nxt, nxt

            first = jax.random.categorical(k, clogits)
            _, toks = jax.lax.scan(step, first, jax.random.split(k, seq_len))
            return jnp.concatenate([first[None], toks[:-1]])

        return jax.vmap(sample_seq)(ks).reshape(tau, batch, seq_len)

    toks = jax.vmap(sample_client)(jax.random.split(key, n_clients), logits)
    return jnp.transpose(toks, (1, 0, 2, 3)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "vocab", "n_clients", "tau", "batch", "seq_len", "heterogeneity",
    "rounds"))
def segment(first_round, seed, *, vocab: int, n_clients: int, tau: int,
            batch: int, seq_len: int, heterogeneity: float,
            rounds: int) -> jax.Array:
    """Tokens [rounds, tau, clients, batch, seq] of rounds ``first_round``,
    ``first_round + 1``, ... — one compiled program for every segment of a
    cell, since the round index and the seed are arguments."""
    logits = client_logits(vocab, n_clients, heterogeneity, seed)
    return jax.lax.map(
        lambda r: sample_round(logits, r, tau=tau, batch=batch,
                               seq_len=seq_len, seed=seed),
        first_round + jnp.arange(rounds))
