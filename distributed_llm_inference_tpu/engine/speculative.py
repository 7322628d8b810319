"""Speculative decoding: draft-model proposals verified by the target model.

Absent from the reference, which decodes strictly one token per
step (``/root/reference/distributed_llm_inference/models/llama/modules.py:73``
gates its whole fast path on ``q_len == 1``).

Greedy speculation: the draft model proposes ``k`` tokens autoregressively;
the target model verifies all of them in ONE forward over ``k+1`` positions
(turning k sequential HBM sweeps into one — the win on bandwidth-bound
decode). The accepted run is the longest prefix where the target's argmax
agrees with the proposal; the target's own argmax at the first disagreement
is appended as the bonus token, so output is IDENTICAL to target-only greedy
decode — speculation changes latency, never content.

Cache rollback is free by design: the static-shape caches advance lengths
explicitly, so rejected positions are simply never counted (writes past
``lengths`` are invisible — validity derives from lengths, ``cache/dense.py``).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cache.dense import DenseKVCache
from ..config import ModelConfig
from ..models import llama

__all__ = ["SpeculativeDecoder"]


class SpeculativeDecoder:
    """Greedy speculative decoding for one sequence (bs=1).

    ``draft_cfg``/``draft_params`` is the small proposal model (same
    tokenizer/vocab as the target).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        draft_cfg: ModelConfig,
        draft_params,
        k: int = 4,
        max_seq_len: int = 512,
        dtype=jnp.bfloat16,
    ):
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError("draft and target must share a vocabulary")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if draft_cfg.loop is not None:
            raise ValueError(
                f"a draft of family {draft_cfg.family!r} (ModelConfig.loop): "
                + llama.LOOP_NEEDS_ONE_STAGE
            )
        self.cfg, self.dcfg = cfg, draft_cfg
        self.params, self.dparams = params, draft_params
        self.k = k
        self.max_seq_len = max_seq_len
        self.dtype = dtype

        # One executable per role; all shapes static in k.
        def prefill(cfg_, params_, tokens, cache, n):
            logits, cache = llama.model_apply(cfg_, params_, tokens, cache, n)
            return logits, cache

        self._prefill_t = jax.jit(
            lambda p, t, c, n: prefill(cfg, p, t, c, n)
        )
        self._prefill_d = jax.jit(
            lambda p, t, c, n: prefill(draft_cfg, p, t, c, n)
        )

        def draft_propose(params_, token, cache):
            """k greedy draft tokens from ``token``; cache advances k."""
            def step(carry, _):
                tok, cache = carry
                logits, cache = llama.model_apply(
                    draft_cfg, params_, tok, cache, jnp.ones((1,), jnp.int32)
                )
                nxt = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
                return (nxt, cache), nxt[0, 0]

            (_, cache), toks = jax.lax.scan(
                step, (token, cache), None, length=self.k
            )
            return toks, cache  # [k], cache advanced by k

        self._propose = jax.jit(draft_propose)

        def target_verify(params_, last_token, proposal, cache):
            """One target forward over [last, p1..pk]; returns the argmax at
            every position ([k+1]) and the cache (advanced k+1 — the caller
            rolls lengths back to the accepted count)."""
            seq = jnp.concatenate([last_token[0], proposal])[None, :]  # [1,k+1]
            logits, cache = llama.model_apply(
                cfg, params_, seq, cache, jnp.full((1,), self.k + 1, jnp.int32)
            )
            preds = jnp.argmax(logits[0], -1).astype(jnp.int32)  # [k+1]
            return preds, cache

        self._verify = jax.jit(target_verify)

        self.stats = {"proposed": 0, "accepted": 0, "steps": 0}

    def _mk_cache(self, cfg: ModelConfig) -> DenseKVCache:
        return DenseKVCache.create(
            cfg.cache_layers, 1, self.max_seq_len, cfg.num_kv_heads,
            cfg.head_dim, self.dtype,
        )

    def generate(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
    ) -> List[int]:
        """Greedy decode; output identical to target-only greedy decoding."""
        n = len(prompt)
        if n == 0:
            raise ValueError("empty prompt")
        if n + max_new_tokens + self.k + 1 > self.max_seq_len:
            raise ValueError("max_seq_len too small for prompt + generation")
        cache_t = self._mk_cache(self.cfg)
        cache_d = self._mk_cache(self.dcfg)
        tokens = jnp.asarray([list(prompt)], jnp.int32)
        nn = jnp.full((1,), n, jnp.int32)

        logits_t, cache_t = self._prefill_t(self.params, tokens, cache_t, nn)
        _, cache_d = self._prefill_d(self.dparams, tokens, cache_d, nn)
        last = int(jnp.argmax(logits_t[0, n - 1]))
        out = [last]

        while len(out) < max_new_tokens and last != eos_token_id:
            last_tok = jnp.asarray([[last]], jnp.int32)
            proposal, cache_d = self._propose(self.dparams, last_tok, cache_d)
            preds, cache_t = self._verify(
                self.params, last_tok, proposal, cache_t
            )
            prop = np.asarray(proposal)
            pred = np.asarray(preds)

            # Longest agreeing prefix; target's pred at the first mismatch is
            # the bonus token (always emitted — preds[i] is conditioned on
            # prop[:i] which all matched).
            accepted = 0
            while accepted < self.k and prop[accepted] == pred[accepted]:
                accepted += 1
            emitted = [int(t) for t in prop[:accepted]] + [int(pred[accepted])]

            self.stats["proposed"] += self.k
            self.stats["accepted"] += accepted
            self.stats["steps"] += 1

            # Roll both caches back to the true sequence length. The target
            # verify advanced k+1 but only [last, d1..d_accepted] are real —
            # the bonus token is not in any cache yet (it is fed next round).
            cache_t = cache_t.replace(
                lengths=cache_t.lengths - (self.k - accepted)
            )
            if accepted == self.k:
                # Full acceptance: the draft consumed [last, d1..d_{k-1}] but
                # never its own final proposal d_k — catch it up one step so
                # its positions stay aligned with the true sequence.
                _, cache_d = self._prefill_d(
                    self.dparams, jnp.asarray([[int(prop[-1])]], jnp.int32),
                    cache_d, jnp.ones((1,), jnp.int32),
                )
            else:
                cache_d = cache_d.replace(
                    lengths=cache_d.lengths - (self.k - accepted - 1)
                )

            for t in emitted:
                out.append(t)
                if len(out) >= max_new_tokens or t == eos_token_id:
                    break
            last = out[-1]

        return out[:max_new_tokens]

    @property
    def acceptance_rate(self) -> float:
        return self.stats["accepted"] / max(self.stats["proposed"], 1)
