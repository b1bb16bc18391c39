"""Autoregressive generation: KV-cache prefill + jitted decode steps.

The reference serves models through external engines; generation here is
native and TPU-shaped: one compiled prefill program (full prompt through
the Pallas flash path writes the KV caches) and one compiled single-token
decode program reused every step — static shapes throughout, so each is
compiled exactly once per (batch, max_len) bucket.

Sampling: greedy, temperature, top-k, nucleus (top-p).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import LlamaConfig, LlamaModel, init_kv_caches


@dataclass(frozen=True)
class SamplingParams:
    max_new_tokens: int = 64
    temperature: float = 0.0   # 0 → greedy
    top_k: int = 0             # 0 → disabled
    top_p: float = 1.0         # 1 → disabled
    eos_token: int | None = None


def sample_logits(logits, rng, params: SamplingParams):
    """logits: (B, V) → tokens (B,)."""
    with jax.named_scope("sample"):
        if params.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / params.temperature
        if params.top_k > 0:
            kth = jax.lax.top_k(logits, params.top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -1e30, logits)
        if params.top_p < 1.0:
            sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # Smallest set whose mass ≥ top_p; keep at least one.
            cutoff_idx = jnp.sum(cum < params.top_p, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(rng, logits, axis=-1)


class Generator:
    """Holds the compiled prefill/decode programs for one (model, bucket).

    Usage::

        gen = Generator(cfg, params, batch=1, max_len=512)
        out = gen.generate(prompt_tokens, SamplingParams(max_new_tokens=32))
    """

    def __init__(self, cfg: LlamaConfig, params, *, batch: int,
                 max_len: int, rng_seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.model = LlamaModel(cfg)
        self._rng = jax.random.PRNGKey(rng_seed)

        model = self.model

        @jax.jit
        def prefill(params, tokens, prompt_len, caches):
            # tokens: (B, max_prompt) right-padded; positions mask pads.
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
            logits, caches = model.apply(params, tokens, positions,
                                         kv_caches=caches)
            # Logits at the last real prompt token per row.
            last = jnp.take_along_axis(
                logits, (prompt_len - 1)[:, None, None], axis=1)[:, 0]
            return last, caches

        @jax.jit
        def decode_step(params, token, pos, caches):
            # token: (B,), pos: (B,) absolute position of `token`.
            logits, caches = model.apply(
                params, token[:, None], pos[:, None], kv_caches=caches)
            return logits[:, 0], caches

        self._prefill = prefill
        self._decode = decode_step

    def _fresh_caches(self):
        return init_kv_caches(self.cfg, self.batch, self.max_len)

    def generate(self, prompt_tokens, params: SamplingParams | None = None
                 ) -> np.ndarray:
        """prompt_tokens: (B, S) array/list, all rows full width S.
        Returns (B, max_new_tokens) (shorter if eos_token ends all rows)."""
        sp = params or SamplingParams()
        prompts = np.asarray(prompt_tokens, dtype=np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None]
        B, S = prompts.shape
        assert B == self.batch, f"generator built for batch={self.batch}"
        assert S + sp.max_new_tokens <= self.max_len, "bucket too small"
        prompt_len = jnp.full((B,), S, jnp.int32)

        caches = self._fresh_caches()
        logits, caches = self._prefill(self.params, jnp.asarray(prompts),
                                       prompt_len, caches)
        out = np.zeros((B, sp.max_new_tokens), np.int32)
        pos = jnp.full((B,), S, jnp.int32)
        rng = self._rng
        finished = np.zeros((B,), bool)
        token = None
        for i in range(sp.max_new_tokens):
            rng, step_rng = jax.random.split(rng)
            token = sample_logits(logits, step_rng, sp)
            tok_np = np.asarray(token)
            out[:, i] = tok_np
            if sp.eos_token is not None:
                finished |= tok_np == sp.eos_token
                if finished.all():
                    out = out[:, : i + 1]
                    break
            if i + 1 < sp.max_new_tokens:
                logits, caches = self._decode(self.params, token, pos, caches)
                pos = pos + 1
        self._rng = rng
        return out


def generate(cfg: LlamaConfig, params, prompt_tokens,
             sampling: SamplingParams | None = None, *,
             max_len: int | None = None) -> np.ndarray:
    """One-shot convenience wrapper around Generator."""
    prompts = np.asarray(prompt_tokens, dtype=np.int32)
    if prompts.ndim == 1:
        prompts = prompts[None]
    sp = sampling or SamplingParams()
    bucket = max_len or min(cfg.max_seq_len,
                            prompts.shape[1] + sp.max_new_tokens)
    gen = Generator(cfg, params, batch=prompts.shape[0], max_len=bucket)
    return gen.generate(prompts, sp)
