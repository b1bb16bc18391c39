"""Follow ONE request of `minicpmsala-serve-longdocs-closed` beside the plain
reference: where `correct` finds an engine's token far under the
reference's best, this says whether a sparse layer's SELECTION explains it
(which of the four (layer, K/V head) tables of that step stood at a
near-tie, whether the reference's own served pass already keeps other
blocks there than its float32 pass, whether the served pass takes the
engine's token), and how often a rounding exchanges a block at all, by
layer, over the request's generated positions.

    chiprun --timeout 1500 -- env FOLLOW_CASES=411897652:0 \
        python3 benchmarks/tools/minicpm_sala_follow.py

FOLLOW_CASES names requests as seed:request, several with commas between;
each is served ALONE through a real `LLMEngine` at the cell's sizes (the
cell's own programs) and teacher-forced through the reference at the
sequence's own length.  `FAULTS_WEIGHTS` (a JSON object) replaces
deviations of `families/minicpm_sala.WEIGHTS`, as in
`tools/minicpm_sala_faults.py`.  FOLLOW_TINY=1 runs the tiny twin under
`tests/benchmarks/minicpm_sala/` (a CPU rehearsal of this script, not a
finding); FOLLOW_OVER (0.1) is the gap from which a position's tables are told.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")


def say(**what):
    """One JSON line, here and in `chiprun_out/sala_follow.jsonl`."""
    line = json.dumps(what)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sala_follow.jsonl"), "a") as f:
        f.write(line + "\n")


def _cell():
    from benchmarks.harness import loader

    if not os.environ.get("FOLLOW_TINY"):
        cell = loader.load_cell("minicpmsala-serve-longdocs-closed")
        return cell.family, cell.config, cell.traffic
    twin = os.path.join(ROOT, "tests", "benchmarks", "minicpm_sala")
    with open(os.path.join(twin, "configs", "tiny-minicpm-sala.json")) as f:
        config = json.load(f)
    with open(os.path.join(twin, "traffic",
                           "tiny-longdocs-closed.json")) as f:
        mix = json.load(f)
    return loader.load_family("minicpm_sala", ROOT), config, mix


def reference_pass(ref, params, sizes, seq, prompt_len: int, level: int):
    """One teacher-forced pass at rounding `level` -> (logits at the rows
    that produced the generated tokens, [a sparse layer's block scores at
    those rows, (rows, Hkv, blocks), -1 where a block did not compete])."""
    import numpy as np

    rows = np.arange(prompt_len - 1, len(seq))
    told = []
    for _, _, x, scores in ref.streams(params, sizes, seq, prompt_len, level,
                                       tell=True):
        if scores is not None:
            told.append(np.asarray(scores[rows]))
    return np.asarray(ref.head_logits(params, sizes, x[rows], level)), told


def kept_of(scores, k: int):
    """(rows, Hkv, blocks) scores -> (the sorted indices of the k blocks a
    stable sort keeps, the margin between the last kept and the first
    dropped; inf where no block is left over)."""
    import numpy as np

    order = np.argsort(-scores, -1, kind="stable")
    top = np.take_along_axis(scores, order, -1)
    full = (scores >= 0).sum(-1) > k
    margin = np.where(full, top[..., k - 1] - top[..., k], np.inf)
    return np.sort(order[..., :k], -1), margin


def follow(family, cfg, params, sizes, engine: dict, prompt: list,
           new_tokens: int) -> dict:
    import numpy as np

    from benchmarks.tools.minicpm_sala_faults import serve

    ref = family.reference
    k = sizes["sparse_config"]["topk"]
    samples, _ = serve(cfg, params, engine, [prompt], new_tokens)
    got = np.asarray(samples[0]["output"])
    seq = list(prompt) + got[:-1].tolist()
    at = np.arange(len(got))
    out = {"prompt_len": len(prompt), "tokens": len(got)}
    by_level = {}
    for level in (0, ref.TIE_PASS - 1):
        lg, told = reference_pass(ref, params, sizes, seq, len(prompt),
                                  level)
        by_level[level] = (lg, [kept_of(s, k) for s in told])
    lg0, sel0 = by_level[0]
    lg3, sel3 = by_level[ref.TIE_PASS - 1]
    top2 = np.partition(lg0, -2, axis=-1)[:, -2:]
    gap = top2[:, 1] - lg0[at, got]
    out["gaps_over_0.02"] = [[int(i), float(gap[i]),
                              float(top2[i, 1] - top2[i, 0])]
                             for i in np.flatnonzero(gap > 0.02)]
    out["engine_is_the_served_pass_best_share"] = float(
        np.mean(lg3.argmax(-1) == got))
    out["engine_is_the_float32_pass_best_share"] = float(
        np.mean(lg0.argmax(-1) == got))
    # what a program that WAS the served pass would be read as, and how far
    # the two passes' logits lie apart over the float32 pass's 32 best
    # tokens (deviation of served - float32 there, a position)
    served_gap = top2[:, 1] - lg0[at, lg3.argmax(-1)]
    out["served_pass_gaps_over_0.02"] = [
        [int(i), float(served_gap[i])]
        for i in np.flatnonzero(served_gap > 0.02)]
    best32 = np.argsort(-lg0, -1)[:, :32]
    shift = np.std(np.take_along_axis(lg3 - lg0, best32, -1), axis=-1)
    out["shift_median"] = float(np.median(shift))
    out["shift_widest"] = sorted(
        [[float(shift[i]), int(i)] for i in np.argsort(-shift)[:6]],
        reverse=True)
    # how often the served pass keeps other blocks than the float32 pass
    out["by_sparse_layer"] = []
    for (kept0, margin0), (kept3, margin3) in zip(sel0, sel3):
        differ = (kept0 != kept3).any(-1)            # (rows, Hkv)
        full = np.isfinite(margin0)
        m = margin0[differ & full]
        out["by_sparse_layer"].append({
            "selections": int(full.sum()),
            "float32_and_served_pass_differ": int((differ & full).sum()),
            "margin_median": float(np.median(margin0[full]))
            if full.any() else None,
            "margins_where_they_differ_quartiles": [
                float(q) for q in np.quantile(m, [0.25, 0.5, 0.75, 1.0])]
            if len(m) else [],
            "rows_that_differ": np.flatnonzero(differ.any(-1)).tolist()[:24],
            "shift_where_they_differ_quartiles": [
                float(q) for q in np.quantile(
                    shift[differ.any(-1)], [0.25, 0.5, 0.75, 1.0])]
            if differ.any() else []})
    # the positions `correct` would refuse
    out["over"] = []
    for i in np.flatnonzero(
            gap > float(os.environ.get("FOLLOW_OVER", 0.1)))[:8]:
        tables = []
        for layer, ((kept0, margin0), (kept3, margin3)) in enumerate(
                zip(sel0, sel3)):
            for h in range(kept0.shape[1]):
                tables.append({
                    "sparse_layer": layer, "kv_head": h,
                    "margin_float32": float(margin0[i, h]),
                    "margin_served": float(margin3[i, h]),
                    "passes_differ": bool(
                        (kept0[i, h] != kept3[i, h]).any())})
        out["over"].append({
            "position": int(i), "gap": float(gap[i]),
            "engine_token": int(got[i]),
            "float32_best": int(lg0[i].argmax()),
            "served_best": int(lg3[i].argmax()),
            "served_pass_gap_of_engine_token": float(
                lg3[i].max() - lg3[i, got[i]]),
            "served_pass_gap_of_float32_best": float(
                lg3[i].max() - lg3[i, lg0[i].argmax()]),
            "tables": tables})
    return out


def main() -> int:
    import jax

    from benchmarks.harness import traffic
    from benchmarks.harness.replica import seeded_params

    family, config, mix = _cell()
    sizes = family.sizes(config)
    family.WEIGHTS.update(json.loads(os.environ.get("FAULTS_WEIGHTS", "{}")))
    cfg = family.program_config(sizes)
    engine = dict(config["serve"]["engine"])
    say(device=str(jax.devices()[0]), weights=family.WEIGHTS)
    for case in os.environ.get("FOLLOW_CASES", "411897652:0").split(","):
        seed, rid = map(int, case.split(":"))
        params = seeded_params(family.model(cfg), seed)
        req = traffic.serve_requests(mix, seed,
                                     sizes["vocab_size"], 45.0)[rid]
        say(case=case, **follow(family, cfg, params, sizes, engine,
                                req.prompt_tokens, req.max_new_tokens))
    return 0


if __name__ == "__main__":
    sys.exit(main())
