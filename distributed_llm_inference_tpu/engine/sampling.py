"""Token sampling as pure jitted functions.

The reference has no sampling at all (no client layer exists — SURVEY §1);
this is part of the client-side capability a complete framework needs. All
samplers are batch-vectorized with *per-row* parameters so one compiled decode
step serves heterogeneous sessions (a greedy row and a top-p row share the
batch), matching the multi-tenant design of the caches.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import struct


class SamplingParams(struct.PyTreeNode):
    """Per-row sampling knobs, shape ``[B]`` each.

    ``temperature == 0`` selects greedy for that row. ``top_k <= 0`` disables
    top-k; ``top_p >= 1`` disables nucleus filtering.

    ``all_greedy`` is STATIC (hashable; part of the jit cache key): the
    all-greedy batch — the common serving case — compiles a decode program
    with no full-vocab sort in it at all (milliseconds per step at
    [112, 32k]); the first stochastic session triggers one recompile to the
    mixed program.
    """

    temperature: jax.Array
    top_k: jax.Array
    top_p: jax.Array
    all_greedy: bool = struct.field(pytree_node=False, default=False)

    @staticmethod
    def create(batch: int, temperature=0.0, top_k=0, top_p=1.0) -> "SamplingParams":
        full = lambda v, dt: jnp.full((batch,), v, dt)
        return SamplingParams(
            temperature=full(temperature, jnp.float32),
            top_k=full(top_k, jnp.int32),
            top_p=full(top_p, jnp.float32),
            all_greedy=temperature <= 0.0,
        )

    @staticmethod
    def stack(rows) -> "SamplingParams":
        return SamplingParams(
            temperature=jnp.asarray([r.temperature for r in rows], jnp.float32),
            top_k=jnp.asarray([r.top_k for r in rows], jnp.int32),
            top_p=jnp.asarray([r.top_p for r in rows], jnp.float32),
            all_greedy=all(r.temperature <= 0.0 for r in rows),
        )


@dataclasses.dataclass(frozen=True)
class SamplingOptions:
    """Host-side per-session options (the scheduler stacks them per step)."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    max_new_tokens: int = 128
    eos_token_id: int = -1  # -1 = never stop on EOS
    # Opt in to draft-model speculative decoding (engines constructed with a
    # draft model only; greedy rows only — stochastic rows decode normally).
    speculative: bool = False


_NEG = jnp.float32(-1e30)


def _filter_top_k_top_p(
    logits: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray
) -> jnp.ndarray:
    """Joint top-k + nucleus filter sharing ONE descending sort (sorting the
    vocab is the dominant cost of stochastic decode ticks).

    Top-k keeps ranks ``< k``; top-p keeps the smallest prefix of the sorted
    distribution with cumulative probability ≥ top_p (rank 0 always survives).
    """
    b, vocab = logits.shape
    sort_idx = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    ranks = jax.lax.broadcasted_iota(jnp.int32, (b, vocab), 1)

    keep_k = (ranks < jnp.clip(top_k, 1, vocab)[:, None]) | (top_k[:, None] <= 0)

    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = ((cum - probs) < top_p[:, None]) | (top_p[:, None] >= 1.0)

    keep = jnp.zeros((b, vocab), bool).at[
        jnp.arange(b)[:, None], sort_idx
    ].set(keep_k & keep_p)
    return jnp.where(keep, logits, _NEG)


def sample(
    logits: jnp.ndarray,
    key: jax.Array,
    params: SamplingParams,
) -> jnp.ndarray:
    """Draw one token per row from ``logits [B, V]`` → ``[B]`` int32.

    Greedy rows (temperature 0) and stochastic rows coexist in one call so the
    decode step stays a single compiled function. ``params.all_greedy`` is
    static: the all-greedy program contains no full-vocab sort at all (the
    sort costs milliseconds at [112, 32k] and is the dominant stochastic-tick
    cost); mixed batches compile the full program once.
    """
    with jax.named_scope("sampler"):  # the device trace's name for it
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if params.all_greedy:
            return greedy

        temp = jnp.maximum(params.temperature, 1e-6)[:, None]
        scaled = logits.astype(jnp.float32) / temp
        scaled = _filter_top_k_top_p(scaled, params.top_k, params.top_p)
        drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

        return jnp.where(params.temperature > 0.0, drawn, greedy)
