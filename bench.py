"""Train-step throughput of a Llama-shaped proxy on one TPU chip.

Prints ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N,
     "device": ...}

One process: it imports JAX, takes the chip, measures, and exits. Where
`jax.devices()` shows no TPU it exits non-zero and prints no result; a CPU
run has no place under a per-chip unit. Any phase that fails, the
long-context one included, fails the run. Start it through the builder's
chip tool, with nothing else holding the chip.

It measures the full jit-compiled training step (forward + backward +
AdamW update, bf16 params/activations, remat) on a ~0.8B-parameter
Llama-2-shaped model, sized so params + Adam state + grads fit one 16GB
v5e chip. `vs_baseline` is measured MFU divided by 0.40, the typical MFU
of the reference's A100 TorchTrainer+NCCL stack on Llama-2 (BASELINE.md
north star: match TorchTrainer+NCCL tokens/sec/chip).
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_MFU = 0.40  # typical A100 TorchTrainer+NCCL MFU on Llama-2


def _log(msg: str) -> None:
    print(f"[bench] {time.strftime('%H:%M:%S')} {msg}", file=sys.stderr,
          flush=True)


def measure() -> int:
    from ray_tpu.util.compile_cache import place_compile_cache
    from ray_tpu.util.device_peaks import device_report, peak_flops_per_s

    cache_dir = place_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log(f"no TPU: jax.devices()[0] is {dev.platform}/{dev.device_kind}; "
             "this benchmark only runs on the chip")
        return 1
    peak = peak_flops_per_s(dev)
    _log(f"{dev.platform}/{dev.device_kind} x{jax.device_count()}, "
         f"compile cache {cache_dir}")

    from dataclasses import replace

    import optax

    from ray_tpu.models import configs, init_params, loss_fn

    # ~0.8B params: fits chip HBM with AdamW state + bf16 grads.
    # dots_nobatch remat saves the non-batch matmul outputs — ~12%
    # faster than full recompute and still fits the 16GB chip.
    # batch 8 x seq 1024 (same 8192 tokens/step as 4x2048) measured
    # ~6% higher MFU: attention's quadratic-in-seq work (uncounted by
    # the 6ND convention both stacks are scored with) shrinks while
    # the counted matmul work stays put.
    # attn_block_q=512 (matching bk) measures ~1% over the 256
    # default at seq 1024: one q block per 512 rows halves the
    # grid's q iterations and both blocks still fit scoped VMEM.
    cfg = replace(
        configs.get_config("llama2-1b"),
        n_layers=12,
        max_seq=1024,
        remat=True,
        remat_policy="dots_nobatch",
        attn_block_q=512,
    )
    steps, warmup = 10, 2

    def _measure(cfg, batch, seq, tag):
        """One measured training run. Every step consumes a FRESH random
        batch (pre-generated on device) so the final loss evidences a
        working step on unseen data rather than memorization of one
        batch."""
        params = init_params(jax.random.PRNGKey(0), cfg)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        optimizer = optax.adamw(1e-4)
        opt_state = jax.jit(optimizer.init)(params)
        _log(f"{tag}: params built: {n_params:,}")

        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        jstep = jax.jit(step, donate_argnums=(0, 1))
        all_tokens = [
            jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(1), i),
                (batch, seq + 1), 0, cfg.vocab_size,
            )
            for i in range(warmup + steps)
        ]

        t0 = time.monotonic()
        for i in range(warmup):
            params, opt_state, loss = jstep(params, opt_state, all_tokens[i])
        jax.block_until_ready(loss)
        _log(f"{tag}: compile+warmup done in {time.monotonic() - t0:.1f}s")

        t0 = time.perf_counter()
        for i in range(steps):
            params, opt_state, loss = jstep(
                params, opt_state, all_tokens[warmup + i]
            )
        # Each step consumes the last one's donated state, so the last
        # loss being ready means every step has run.
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0

        tokens_per_sec = batch * seq * steps / dt
        # 6ND training FLOPs convention (fwd 2ND + bwd 4ND), ignoring
        # remat recompute — the same convention baseline MFU numbers use.
        mfu = tokens_per_sec * 6.0 * n_params / peak
        # 6ND ignores attention's quadratic matmuls, which at long seq
        # are a real double-digit share of the chip's work: QK^T + PV
        # fwd ~= 2*seq_avg*2*d_attn per layer-token, x3 for training.
        d_attn = cfg.n_heads * cfg.head_dim
        attn_flops_per_token = 6.0 * cfg.n_layers * seq * d_attn / 2 * 2
        mfu_attn = (
            tokens_per_sec * (6.0 * n_params + attn_flops_per_token) / peak
        )
        return {
            "value": round(tokens_per_sec, 1),
            "mfu": round(mfu, 4),
            "mfu_with_attention": round(mfu_attn, 4),
            "batch": batch,
            "seq": seq,
            "params": n_params,
            "loss": float(loss),
        }

    result = _measure(cfg, 8, 1024, "seq1024")
    # Long-context variant: same 0.8B proxy at seq 4096 with the
    # flash-attention kernel in the hot path. batch x seq stays 8192
    # tokens/step.
    long_context = _measure(replace(cfg, max_seq=4096), 2, 4096, "seq4096")
    print(json.dumps({
        "metric": "llama2(0.8B) train-step tokens/s/chip",
        "unit": "tokens/s/chip",
        "vs_baseline": round(result["mfu"] / BASELINE_MFU, 3),
        "device": device_report(),
        **result,
        "long_context": long_context,
    }), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Roll-up: one per-PR trajectory record over every bench artifact.
# ---------------------------------------------------------------------------
# Headline fields, in preference order: the number each bench's gate
# actually reads. A metric entry contributes its first match (or its
# first numeric field as a fallback) so the roll-up stays one line.
_ROLLUP_HEADLINE_KEYS = (
    "overhead_pct", "vs_baseline", "value", "ok", "p99_ms", "p50_ms",
    "e2e_sum_ok", "tokens_per_s", "emit_us", "cost_us_per_step",
)


def rollup() -> int:
    """Aggregate every BENCH_*.json's gate numbers into one trajectory
    record appended to PROGRESS.jsonl (kind="bench_rollup" distinguishes
    it from the driver's wall-clock records)."""
    import glob

    gates = {}
    for path in sorted(glob.glob("BENCH_*.json")):
        name = os.path.splitext(os.path.basename(path))[0]
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            gates[name] = {"error": "unreadable"}
            continue
        entries = doc if isinstance(doc, list) else [doc]
        file_gates = {}
        for e in entries:
            if not isinstance(e, dict):
                continue
            metric = str(e.get("metric") or e.get("name") or "?")
            headline = None
            for k in _ROLLUP_HEADLINE_KEYS:
                if isinstance(e.get(k), (int, float, bool)):
                    headline = {k: e[k]}
                    break
            if headline is None:
                headline = next(
                    ({k: v} for k, v in e.items()
                     if k not in ("ts", "steps", "rounds")
                     and isinstance(v, (int, float))
                     and not isinstance(v, bool)),
                    {},
                )
            file_gates[metric] = headline
        gates[name] = file_gates
    rec = {
        "ts": time.time(),
        "kind": "bench_rollup",
        "files": len(gates),
        "metrics": sum(len(g) for g in gates.values()),
        "gates": gates,
    }
    with open("PROGRESS.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps({"kind": "bench_rollup", "files": rec["files"],
                      "metrics": rec["metrics"]}), flush=True)
    return 0


def main() -> int:
    if len(sys.argv) >= 2 and sys.argv[1] == "--rollup":
        return rollup()
    return measure()


if __name__ == "__main__":
    sys.exit(main())
