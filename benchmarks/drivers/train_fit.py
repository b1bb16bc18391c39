"""A training job: `JaxTrainer(...).fit()` with one worker that holds the
cell's chips; steps for `--seconds`, loss reported through `train.report`.
This process only starts the job and reads what the worker reports."""

from __future__ import annotations

import math
import threading
import time

from benchmarks.harness import cluster, kernel_costs
from benchmarks.harness.loader import BenchmarkError

FIT_DEADLINE_S = 1500.0
# Random weights give logits of about unit variance, which puts the first
# loss about 0.5 above ln V (PR 21 read 12.24 for ln 128256 = 11.76); a
# sanity band -- the precise check is the reference's loss on the same rows.
FIRST_LOSS_BAND = (0.0, 1.0)
REFERENCE_TOLERANCE = 0.02     # bf16 step against the float32 reference


def run(cell, seed, seconds, trace, t_start, platform, log) -> dict:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    from benchmarks.harness.train_worker import train_loop

    sizes = cell.family.sizes(cell.config)
    tr = cell.config["train"]
    cluster.require_tpu_resource(cell.chips)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "sizes": sizes, "family": cell.family_name, "root": cell.root,
            "train": tr, "traffic": cell.traffic,
            "seed": seed, "seconds": seconds, "trace": trace,
            "chips": cell.chips,
            "reference_rows": int(tr["reference_rows"])},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpu_chips_per_worker=cell.chips))
    box: dict = {}

    def fit():
        try:
            box["result"] = trainer.fit()
        except BaseException as e:  # noqa: BLE001 -- re-raised below
            box["error"] = e

    t_fit = time.monotonic()
    t = threading.Thread(target=fit, daemon=True)
    t.start()
    t.join(FIT_DEADLINE_S)
    if t.is_alive():
        raise BenchmarkError(f"JaxTrainer.fit() still running after "
                             f"{FIT_DEADLINE_S:.0f}s")
    if "error" in box:
        raise box["error"]
    result = box["result"]
    final = result.metrics
    if final.get("kind") != "final":
        raise BenchmarkError(f"trainer ended without its final report: "
                             f"{final}")
    who = final["worker"]
    released_s = cluster.wait_for_exit(who["pid"])
    cluster.check_lease_holder(who, cell.chips, platform)

    steps = final["steps"]
    window_s = final["t_close"] - final["t_open"]
    tokens_per_step = final["rows"] * final["seq"]
    tokens_per_s = len(steps) * tokens_per_step / window_s / cell.chips
    setup_s = final["t_open"] - t_start
    worker_ready_s = who["t_report"] - t_fit
    reported = [m for m in result.metrics_history if m.get("kind") == "step"]
    log(phase="setup", worker_ready_s=worker_ready_s,
        init_state_s=final["init_state_s"], compile_s=final["compile_s"],
        setup_s=setup_s, compiled_bytes=final["compiled_bytes"],
        kernel_calls=final["kernel_calls"],
        compile_cache_dir=who["compile_cache_dir"])
    log(phase="load", steps=len(steps), window_s=window_s,
        tokens_per_step=tokens_per_step, documents=final["documents"],
        first_losses=final["losses"], last_loss=steps[-1]["loss"],
        reference_loss=final["reference_loss"],
        model_loss=final["model_loss"], released_s=released_s,
        reports_received=len(reported),
        compiles_in_window=final["compiles_in_window"])

    problems = []
    losses = final["losses"] + [s["loss"] for s in steps]
    if not all(math.isfinite(x) for x in losses):
        problems.append("a loss is not finite")
    above = final["losses"][0] - final["log_vocab"]
    if not FIRST_LOSS_BAND[0] <= above <= FIRST_LOSS_BAND[1]:
        problems.append(f"first loss {final['losses'][0]:.4f} is not in ln V "
                        f"+ {FIRST_LOSS_BAND} (ln V = "
                        f"{final['log_vocab']:.4f})")
    # (Single losses are noisy at small batches: the last five are averaged.)
    tail = [s["loss"] for s in steps[-5:]]
    if not sum(tail) / len(tail) < final["losses"][0]:
        problems.append("the loss did not fall over the window")
    if final["compiles_in_window"]:
        problems.append(f"{final['compiles_in_window']} programs were "
                        "lowered or compiled inside the window")
    if len(reported) != len(steps):
        problems.append(f"{len(reported)} step reports reached the driver "
                        f"for {len(steps)} steps")
    if final["reference_loss"] is None:
        problems.append("the reference was not run")
    elif abs(final["reference_loss"] - final["model_loss"]) > \
            REFERENCE_TOLERANCE:
        problems.append(
            f"loss {final['model_loss']:.4f} against the reference's "
            f"{final['reference_loss']:.4f} on the same rows "
            f"(tolerance {REFERENCE_TOLERANCE})")
    if platform == "tpu" and not final["kernel_calls"]:
        problems.append("the step holds no tpu_custom_call: flash attention "
                        "fell to interpret mode")

    end_to_end = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
    device = {"platform": who["platform"], "kind": who["kind"],
              "count": who["count"],
              "memory_peak_bytes": final["memory_peak_bytes"]}
    obs = {"sizes": sizes, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family_name,
           "device": device, "seconds": seconds, "steps": steps,
           "tokens_per_step": tokens_per_step, "rows": final["rows"],
           "seq": final["seq"], "trace": final["trace"],
           "worker_ready_s": worker_ready_s, "end_to_end": end_to_end,
           "peaks": kernel_costs.peaks(who["kind"])
           if who["platform"] == "tpu" else None}
    checked = [
        f"first loss - ln V = {above:.4f}, limits {FIRST_LOSS_BAND}",
        f"loss {final['model_loss']} against the reference's "
        f"{final['reference_loss']} on the same rows, limit "
        f"{REFERENCE_TOLERANCE}"]
    return {"correct": not problems, "problems": problems,
            "checked": checked, "attempted": len(steps),
            "failed": sum(1 for s in steps if not math.isfinite(s["loss"])),
            "end_to_end": end_to_end, "device": device, "obs": obs}
