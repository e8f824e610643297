"""Serve a small model with batched requests through the DES engine.

The continuous-batching control plane is the paper's DES scheduler:
request arrivals/prefills/decodes are events; runs of decode events in
the lookahead window execute as pre-composed fused k-step programs.

    PYTHONPATH=src python examples/serve_lm.py
"""

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.models import LM
from repro.serving.engine import ServingEngine


def main():
    use_compile_cache()
    cfg = get_config("phi4-mini-3.8b").reduced()
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_slots=4, max_len=128,
                           max_batch_len=6, arrival_lookahead=7.0)

    rng = np.random.default_rng(1)
    t = 0.0
    for rid in range(8):
        prompt = rng.integers(0, cfg.vocab_size, rng.integers(4, 12)).tolist()
        engine.submit(rid, prompt, max_new_tokens=10, at=t)
        t += 7.0 + float(rng.random() * 2)
    engine.schedule_decode_grid(1.0, t + 80.0)

    stats = engine.run()
    print(f"requests served: "
          f"{sum(r.done for r in engine.requests.values())}/8")
    print(f"decode events {stats.decode_events}; "
          f"fused batches {stats.fused_batches} "
          f"(mean run length {stats.mean_fused_length:.2f}); "
          f"single-step fallbacks {stats.singles}")
    print(f"composed programs: {sorted(stats.compiled_programs)}")
    for rid, r in sorted(engine.requests.items()):
        print(f"  req {rid}: {len(r.output)} tokens, "
              f"latency {(r.finish_time - r.arrival):.1f} sim-steps")


if __name__ == "__main__":
    main()
