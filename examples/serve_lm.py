"""Serve a small model with batched requests: continuous batching +
paged KV cache + the NFL page table (the paper's technique in the serving
data plane).

  PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.kernels.backend import enable_compile_cache
from repro.models.model import build_model
from repro.serve.kv_cache import PagedKVCache, PagedKVConfig
from repro.serve.prefix_cache import composite_key
from repro.serve.scheduler import ContinuousBatcher, Request, ServeConfig


def main():
    enable_compile_cache()
    cfg = get_config("qwen3-14b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # --- continuous batching over 10 concurrent requests
    batcher = ContinuousBatcher(model, params,
                                ServeConfig(batch_slots=4, max_len=96))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                    max_new_tokens=12)
            for i in range(10)]
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    batcher.run_until_drained()
    dt = time.perf_counter() - t0
    tok = sum(len(r.output) for r in reqs)
    print(f"continuous batching: {tok} tokens in {dt:.2f}s "
          f"({tok / dt:.0f} tok/s, {batcher.steps} steps)")

    # --- paged KV cache backed by the NFL page table
    kv = PagedKVCache(PagedKVConfig(
        n_pages=256, page_size=8, n_layers=cfg.n_layers,
        kv_heads=cfg.attn.kv_heads, head_dim=cfg.attn.head_dim))
    for sid in (101, 202, 303):
        kv.register_sequence(sid)
        for _ in range(20):
            k = jax.random.normal(jax.random.PRNGKey(sid),
                                  (cfg.n_layers, cfg.attn.kv_heads,
                                   cfg.attn.head_dim))
            kv.append(sid, k, k)
    k, v, n = kv.gather_kv(202)
    print(f"paged KV: gathered [{k.shape}] for seq 202 (len={n})")
    print("NFL page-table stats:", kv.stats()["table"])
    # batched page-table probe: one vectorized lookup for 64 blocks
    pages = kv.lookup_pages(101, 3)
    print("pages of seq 101:", pages.tolist())


if __name__ == "__main__":
    main()
