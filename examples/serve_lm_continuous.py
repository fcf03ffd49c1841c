"""Continuous-batching LM serving: requests of different prompt lengths
join and leave the slot pool mid-flight (vLLM-style scheduler).

    PYTHONPATH=src python examples/serve_lm_continuous.py
"""
import numpy as np

import jax

from repro.configs import registry
from repro.models import transformer as tfm
from repro.serving.batcher import ContinuousBatcher, Request
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = registry.get_reduced("llama3.2-1b")
    params, _ = tfm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    cb = ContinuousBatcher(cfg, params, slots=3, max_len=32)
    n_req = 9
    for i in range(n_req):
        plen = int(rng.integers(2, 8))
        cb.submit(Request(rid=i,
                          prompt=rng.integers(0, cfg.vocab_size,
                                              plen).astype(np.int32),
                          max_new=6))
    steps = cb.run()
    st = cb.stats()
    naive = sum(len(r.prompt) + 6 - 1 for r in cb.done)
    print(f"served {st['completed']} requests in {steps} scheduler steps "
          f"(sequential would take {naive})")
    print(f"latency p50 {st['p50_ms']:.0f} ms  p95 {st['p95_ms']:.0f} ms  "
          f"p99 {st['p99_ms']:.0f} ms, p50 TTFT "
          f"{st['p50_ttft_s'] * 1e3:.0f} ms")
    assert st["completed"] == n_req and steps < naive
    print("continuous batching beats sequential scheduling ✓")


if __name__ == "__main__":
    main()
