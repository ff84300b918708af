"""Smoke run of the main path on TPU, at the full width of smollm-135m.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: sharded train step vs one chip

One chip: ``repro.launch.train`` runs 10 steps (4 x 2048 tokens each) and
the losses must be finite, start near ln(vocab) and fall;
``repro.launch.serve`` serves 8 requests (prompts of 64-512 tokens, 32 new
tokens each), every request must get all 32 tokens, and one request's
greedy tokens must equal the argmax of an uncached ``forward``.

Four chips: one ``jit_train_step`` on a (2 data, 2 model) mesh against the
same step jitted on one chip; loss and updated params must agree.

Weights and data are random, made from a fixed seed. Every phase runs in
this one process. Any failed check raises, so the exit code is non-zero
and the last line is not printed. Without a TPU the script stops before
the first phase. The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Step times are smoke readings of one run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig  # noqa: E402
from repro.data.pipeline import lm_batch  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.parallel import build_mesh, plan_memory  # noqa: E402
from repro.train.train_step import (  # noqa: E402
    init_train_state,
    jit_train_step,
    make_train_step,
)

ARCH = "smollm-135m"
SEED = 0


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def train_phase(arch: str = ARCH, reduced: bool = False, steps: int = 10,
                batch: int = 4, seq: int = 2048) -> None:
    # Peak lr 1e-3: at the launcher's default 3e-3, with a one-step
    # warm-up, Adam's first updates lift the loss above its start for a
    # few steps, and after 10 steps it is barely below it.
    summary = train.main([
        "--arch", arch, *(["--reduced"] if reduced else []),
        "--steps", str(steps), "--lr", "1e-3", "--global-batch", str(batch),
        "--seq-len", str(seq), "--log-interval", "1", "--seed", str(SEED)])
    rows = summary["metrics_log"]
    losses = [row["loss"] for row in rows]
    times = [row["time_s"] for row in rows]
    vocab = get_config(arch, reduced=reduced).padded_vocab  # softmax width
    print(f"train: {len(losses)} steps of {batch}x{seq} tokens, "
          f"losses {losses}")
    require(len(losses) == steps, f"{steps} logged steps, got {len(losses)}")
    require(all(math.isfinite(x) for x in losses), "every loss is finite")
    require(abs(losses[0] - math.log(vocab)) <= 1.0,
            f"first loss {losses[0]} within 1.0 of ln({vocab})")
    require(losses[-1] < losses[0], "the last loss is below the first")
    print(f"train: compile_s={summary['compile_s']} "
          f"median_step_s_after_warmup={statistics.median(times[1:])} "
          "(smoke reading, not a benchmark number)")


def serve_phase(arch: str = ARCH, reduced: bool = False,
                num_requests: int = 8, prompt_len=(64, 513),
                new_tokens: int = 32, max_batch: int = 4,
                max_seq: int = 1024) -> None:
    # fp32 matmuls at full precision, so that the engine's cached decode
    # and the uncached forward below agree to float32 rounding and the
    # greedy comparison is exact.
    with jax.default_matmul_precision("highest"):
        done = serve.main([
            "--arch", arch, *(["--reduced"] if reduced else []),
            "--num-requests", str(num_requests),
            "--max-batch", str(max_batch), "--max-seq", str(max_seq),
            "--max-new-tokens", str(new_tokens),
            "--prompt-len", str(prompt_len[0]), str(prompt_len[1]),
            "--seed", str(SEED)])
        require(sorted(r.uid for r in done) == list(range(num_requests)),
                f"all {num_requests} requests finished")
        for r in done:
            require(len(r.out_tokens) == new_tokens,
                    f"request {r.uid} got {len(r.out_tokens)} tokens, "
                    f"not {new_tokens}")
        # Greedy check: teacher-force the engine's own tokens through an
        # uncached forward of the same weights (same seed as the launcher).
        cfg = get_config(arch, reduced=reduced)
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(SEED), cfg,
                                   dtype=jnp.float32)
        req = min(done, key=lambda r: r.uid)
        tokens = np.concatenate([req.prompt, req.out_tokens[:-1]])
        logits, _, _ = jax.jit(lambda p, t: model.forward(p, cfg, t))(
            params, jnp.asarray(tokens, jnp.int32)[None])
        logits = np.asarray(logits[0, len(req.prompt) - 1:], np.float32)
    want = logits.argmax(-1).tolist()
    top2 = np.sort(logits, axis=-1)[:, -2:]
    print(f"serve: {len(done)} requests, prompt lengths "
          f"{sorted(len(r.prompt) for r in done)}, {new_tokens} tokens each")
    print(f"serve: request {req.uid} greedy tokens {req.out_tokens}; "
          f"uncached forward argmax {want}; smallest top-1/top-2 logit gap "
          f"{float((top2[:, 1] - top2[:, 0]).min())}")
    require(req.out_tokens == want,
            "greedy tokens equal the uncached forward's argmax")


def four_chip_phase(arch: str = ARCH, reduced: bool = False, batch: int = 8,
                    seq: int = 512) -> None:
    """One train step on a (2 data, 2 model) mesh vs the same step on one
    chip, fp32 params, at test_dp_tp_grad_equivalence's tolerances."""
    cfg = get_config(arch, reduced=reduced)
    plan = plan_memory(cfg, tp=2, dp=2)
    state = init_train_state(cfg, plan, jax.random.PRNGKey(SEED),
                             dtype=jnp.float32)
    data = lm_batch(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                               global_batch=batch, seed=SEED), 0)
    step_rng = jax.random.PRNGKey(SEED + 1)

    t0 = time.monotonic()   # uncommitted arrays: this runs on device 0
    ref_state, ref_metrics = jax.jit(make_train_step(cfg, plan))(
        state, data, step_rng)
    ref_loss = float(ref_metrics["loss"])
    ref_params = jax.device_get(ref_state["params"])
    del ref_state
    print(f"4-chip: one-chip reference step {time.monotonic() - t0} s "
          f"(compile included), loss {ref_loss}")

    mesh = build_mesh((2, 2), ("data", "model"))
    with mesh:
        step = jit_train_step(cfg, plan, mesh, jax.eval_shape(lambda: state),
                              jax.eval_shape(lambda: data), donate=False)
        t0 = time.monotonic()
        out_state, metrics = step(state, data, step_rng)
        loss = float(metrics["loss"])
    print(f"4-chip: sharded step {time.monotonic() - t0} s "
          f"(compile included), loss {loss}")
    for name, leaf in (
            ("params/dense_ffn/wg", out_state["params"]["dense_ffn"]["wg"]),
            ("opt/m/dense_ffn/wg", out_state["opt"]["m"]["dense_ffn"]["wg"])):
        shards = {s.device.id: s.data.nbytes for s in leaf.addressable_shards}
        print(f"4-chip: {name} {leaf.nbytes} bytes, per device {shards}")
        require(len(shards) == 4 and max(shards.values()) < leaf.nbytes,
                f"{name} is spread over the four devices")
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-4, atol=2e-4)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(out_state["params"]),
                    jax.tree.leaves(ref_params)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)
        worst = max(worst, float(np.abs(a - b).max()))
    print(f"4-chip: loss |diff| {abs(loss - ref_loss)}, "
          f"params max |diff| {worst}")


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    print(f"device: kind={dev.device_kind} count={len(devices)}", flush=True)
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} devices")
    if args.chips == 4:
        four_chip_phase()
    else:
        train_phase()
        print(f"train: peak_bytes_in_use="
              f"{dev.memory_stats()['peak_bytes_in_use']}", flush=True)
        serve_phase()
        print(f"serve: peak_bytes_in_use="
              f"{dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
