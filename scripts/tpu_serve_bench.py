"""On-chip LLM serving benchmark: paged continuous-batching decode
throughput on the real TPU (BASELINE.md benchmark config row:
"batched-inference Serve replicas on v5e").

Measures the LLMEngine in paged-KV mode with a ~1.2B-parameter decoder:
a batch of concurrent streams decode together; throughput is aggregate
generated tokens/sec. Prints one JSON line per configuration.

Refuses to run on CPU (the interpret-mode path is covered by
tests/test_serve_llm.py + test_llm_paged.py).

Usage: PYTHONPATH=/root/repo python scripts/tpu_serve_bench.py
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    if jax.default_backend() != "tpu":
        sys.exit(f"tpu_serve_bench: an on-chip benchmark, and the JAX "
                 f"backend here is {jax.default_backend()!r}")

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.serve.llm import LLMEngine, SamplingParams

    # Same 1.2B-class decoder as bench.py, sized for serving.
    cfg = LlamaConfig(vocab_size=32000, d_model=2048, n_layers=16,
                      n_heads=16, n_kv_heads=16, d_ff=8192,
                      max_seq_len=2048, dtype=jnp.bfloat16,
                      attention="flash", remat=False)
    model = LlamaModel(cfg)
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))

    for batch, new_tokens, chunk in ((16, 128, 64), (32, 128, 64)):
        engine = LLMEngine(cfg, params, max_batch=batch, max_len=512,
                           decode_chunk=chunk, page_size=64,
                           kv_pool_tokens=batch * 512 + 512)
        prompts = [list(rng.integers(1, cfg.vocab_size, 64))
                   for _ in range(batch)]
        sp = SamplingParams(max_new_tokens=new_tokens, temperature=0.0)
        # Warm: compile the batched prefill + decode programs with a
        # burst (a single warm request would leave prefill_many's first
        # compile inside the timed window).
        warm = [engine.submit(p[:64], SamplingParams(max_new_tokens=8,
                                                     temperature=0.0))
                for p in prompts[: min(len(prompts), 8)]]
        for h in warm:
            h.tokens()

        t0 = time.perf_counter()
        handles = [engine.submit(p, sp) for p in prompts]
        outs = [h.tokens() for h in handles]
        dt = time.perf_counter() - t0
        total = sum(len(o) for o in outs)
        print(json.dumps({
            "metric": "llm_paged_decode_tokens_per_s",
            "value": round(total / dt, 1),
            "unit": "tokens/s",
            "extra": {
                "batch": batch, "prompt_len": 64,
                "new_tokens_per_stream": new_tokens,
                "total_generated": total,
                "wall_s": round(dt, 2),
                "decode_chunk": chunk,
                "params_millions": 1205,
                "device": {"platform": jax.devices()[0].platform,
                           "kind": jax.devices()[0].device_kind,
                           "count": len(jax.devices())},
                "paged": True, "page_size": 64,
            },
        }), flush=True)
        engine.shutdown()


if __name__ == "__main__":
    main()
