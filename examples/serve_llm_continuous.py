"""Continuous-batching LLM serving: requests join a running decode loop.

The upgrade over examples/serve_llm.py's static batcher (the reference's
serve.batching model): a paged KV cache over decode slots lets requests enter at any
decode-step boundary and leave when they finish, so mixed arrival times
keep the chip busy. Per-request sampling (temperature/top_k/top_p)
shares the same decode batch as greedy requests.

Run: python examples/serve_llm_continuous.py
"""

import threading
import time
from dataclasses import replace

import numpy as np

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu.serve.llm import llm_deployment


def load_model():
    import jax

    from ray_tpu.models import configs, init_params

    cfg = replace(configs.tiny, dtype=np.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def main():
    rt.init(num_cpus=4)
    app = llm_deployment(load_model, num_slots=4, max_len=128,
                         default_max_new_tokens=16)
    handle = serve.run(app, name="llm")

    # Mixed arrivals: three clients fire at staggered times; each joins
    # the running decode loop at the next step boundary.
    results = {}

    def client(name, prompt, delay, **sampling):
        time.sleep(delay)
        t0 = time.perf_counter()
        toks = rt.get(handle.remote(prompt, **sampling), timeout=300)
        results[name] = (toks, time.perf_counter() - t0)

    threads = [
        threading.Thread(target=client, args=("greedy", [1, 7, 42], 0.0)),
        threading.Thread(target=client, args=("sampled", [9, 3], 0.1),
                         kwargs={"temperature": 0.8, "top_k": 40}),
        threading.Thread(target=client, args=("late", [5, 5, 5, 5], 0.3)),
    ]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for name, (toks, dt) in results.items():
        print(f"{name:8s} {dt:5.2f}s  tokens={toks}")

    # Token streaming rides the same engine.
    print("stream:", list(
        handle.options(stream=True, method_name="stream").remote([2, 4, 8])
    ))
    serve.shutdown()
    rt.shutdown()


if __name__ == "__main__":
    main()
