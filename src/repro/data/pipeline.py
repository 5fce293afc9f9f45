"""Synthetic token pipeline: deterministic, shardable, elastic.

Generates next-token-prediction batches from a seeded Markov-ish stream so
training losses actually descend (the model can learn the transition
structure).  The loader is *elastic*: batches are a pure function of
(seed, step), so after a job resize every slice can regenerate its shard of
the global batch without coordination — the data-pipeline analogue of the
paper's requirement that reconfiguration not lose application progress.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 1234
    frontend: Optional[str] = None    # "patches" | "frames"
    frontend_tokens: int = 0
    d_model: int = 0
    enc_dec: bool = False


class SyntheticLMData:
    """Deterministic synthetic LM stream."""

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        self.k = min(cfg.vocab_size, 4093)
        self.shift = int(rng.integers(1, self.k))

    def batch(self, step: int) -> Dict[str, jax.Array]:
        cfg = self.cfg
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
        text_len = cfg.seq_len - cfg.frontend_tokens
        if cfg.enc_dec:
            text_len = cfg.seq_len // 2
        base = jax.random.randint(key, (cfg.global_batch, 1), 0, self.k)
        steps = jnp.arange(text_len + 1)[None, :]
        toks = (base + steps * self.shift) % self.k   # learnable structure
        noise = jax.random.bernoulli(key, 0.1, toks.shape)
        rnd = jax.random.randint(key, toks.shape, 0, self.k)
        toks = jnp.where(noise, rnd, toks).astype(jnp.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if cfg.frontend:
            fkey = jax.random.fold_in(key, 1)
            batch["frontend"] = jax.random.normal(
                fkey, (cfg.global_batch,
                       cfg.frontend_tokens or cfg.seq_len // 2,
                       cfg.d_model), jnp.float32)
        return batch


def make_batch(cfg: DataConfig, step: int) -> Dict[str, jax.Array]:
    return SyntheticLMData(cfg).batch(step)
