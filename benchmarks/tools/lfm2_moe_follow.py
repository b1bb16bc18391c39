"""Follow ONE request of `lfm2moe-serve-agents-closed` through the program,
layer by layer and position by position, beside the plain reference: where
`correct` finds an engine's token far under the reference's best, this
says which router selection of which position and layer differs, by how
much the program's scores lay off there, what the stream had on it going
in, and whether the program's ARITHMETIC is sound once it is made to take
the reference's experts.

    chiprun --timeout 1500 -- env FOLLOW_CASES=4200000401:155 \
        python3 benchmarks/tools/lfm2_moe_follow.py

FOLLOW_CASES names requests as seed:request, several with commas between;
each is served alone and judged by the harness's own rule first
(`replica.check_reference` and `serve_common.judge`, as a run of the cell
judges its samples); FOLLOW_JUDGE_ONLY=1 stops there.

Witnesses, each teacher-forced over the engine's own tokens:
  reference    `reference/lfm2_moe.py`, float32 at "highest", on the device
  natural      the serving family's `prefill` of the prompt, then `decode`
               steps through pages and conv windows (what the engine runs),
               with `route` made to tell its scores and its selection
  oracle       the same, `route` made to take the REFERENCE's experts: what
               is left between its logits and the reference's is arithmetic
  cpu          the reference on the host's CPU at the positions in question
               (FOLLOW_CPU=0 leaves it out)

FOLLOW_TINY=1 runs the tiny twin under `tests/benchmarks/lfm2_moe/` (a CPU
rehearsal of this script, not a finding).
"""

from __future__ import annotations

import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")


def say(**what):
    """One JSON line, here and in `chiprun_out/follow.jsonl` (the end of a
    long output is all that a chip call hands back)."""
    line = json.dumps(what)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "follow.jsonl"), "a") as f:
        f.write(line + "\n")


def _cell():
    from benchmarks.harness import loader

    if not os.environ.get("FOLLOW_TINY"):
        cell = loader.load_cell("lfm2moe-serve-agents-closed")
        return cell.family, cell.config, cell.traffic
    twin = os.path.join(ROOT, "tests", "benchmarks", "lfm2_moe")
    with open(os.path.join(twin, "configs", "tiny-lfm2-moe.json")) as f:
        config = json.load(f)
    with open(os.path.join(twin, "traffic", "tiny-agents-closed.json")) as f:
        mix = json.load(f)
    return loader.load_family("lfm2_moe", ROOT), config, mix


def capturing(mod, fn):
    """`fn(params, *args)` jitted as `(params, forced, use, *args)` ->
    (its outputs, [(biased scores, selection)] a routed layer, [stream
    after each layer]); where `use`, `route` takes `forced[layer]`."""
    import jax
    import jax.numpy as jnp

    def run(params, forced, use, *args):
        told, streams = [], []

        def route(logits, bias, top_k):
            s = jax.nn.sigmoid(logits.astype(jnp.float32))
            biased = s + bias.astype(jnp.float32)
            _, idx = jax.lax.top_k(biased, top_k)
            idx = jnp.where(use, forced[len(told)], idx)
            chosen = jnp.take_along_axis(s, idx, axis=-1)
            told.append((biased, idx))
            return idx, chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)

        sound = mod.Layer.mix

        def mix(self, x, operator, counts, valid=None):
            out = sound(self, x, operator, counts, valid)
            streams.append(out[0])
            return out

        with mock.patch.object(mod, "route", route), \
                mock.patch.object(mod.Layer, "mix", mix):
            out = fn(params, *args)
        return out, told, streams

    return jax.jit(run)


def through_the_program(fam, params, prompt, fed, page, forced=None):
    """Prefill of `prompt`, then one decode step for each token of `fed`
    -> logits (1 + len(fed), V), biased scores and selections (layers,
    S, ...) and streams (all layers, S, d) over S = len(prompt) +
    len(fed) positions.  `forced` (layers, S, k): the experts to take."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import lfm2_moe as mod

    cfg = fam.cfg
    P, S = len(prompt), len(prompt) + len(fed)
    L, k = cfg.n_expert_layers, cfg.top_k
    bucket = max(page, 1 << (P - 1).bit_length())
    n_pages = -(-max(S, bucket) // page) + 1
    use = forced is not None
    if forced is None:
        forced = np.zeros((L, S, k), np.int32)
    prefill = capturing(mod, fam.prefill)
    decode = capturing(mod, fam.decode)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :P] = prompt
    first = np.zeros((L, bucket, k), np.int32)
    first[:, :P] = forced[:, :P]
    (logits, fresh, _), told, streams = prefill(
        params, jnp.asarray(first), use, jnp.asarray(padded),
        jnp.asarray([P - 1]))
    out = [np.asarray(logits)]
    scores = [[np.asarray(b)[:P]] for b, _ in told]
    chosen = [[np.asarray(i)[:P]] for _, i in told]
    xs = [[np.asarray(x)[0, :P]] for x in streams]
    state = fam.init_state(1, n_pages + 1, page)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    state = fam.write_prompt(state, fresh, jnp.asarray([0]),
                             table[:, : bucket // page])
    live = jnp.asarray([True])
    for j, tok in enumerate(fed):
        at = jnp.asarray([P + j], jnp.int32)
        (logits, state, _), told, streams = decode(
            params, jnp.asarray(forced[:, P + j: P + j + 1]), use,
            jnp.asarray([tok], jnp.int32), at, state, table, at, live)
        out.append(np.asarray(logits))
        for i, (b, ix) in enumerate(told):
            scores[i].append(np.asarray(b))
            chosen[i].append(np.asarray(ix))
        for i, x in enumerate(streams):
            xs[i].append(np.asarray(x)[0])
    cat = lambda rows: np.stack([np.concatenate(r, 0) for r in rows])  # noqa: E731
    return np.concatenate(out, 0), cat(scores), cat(chosen), cat(xs)


def through_the_reference(ref, params, sizes, seq):
    """-> logits (S, V), biased scores (layers, S, E), streams (all layers,
    S, d) of the plain reference over `seq`."""
    import jax.numpy as jnp
    import numpy as np

    p = params["params"]
    x = p["embed"]["embedding"][jnp.asarray(seq)].astype(jnp.float32)
    args = dict(n_heads=sizes["num_attention_heads"],
                n_kv_heads=sizes["num_key_value_heads"],
                theta=float(sizes["rope_parameters"]["rope_theta"]),
                eps=float(sizes["norm_eps"]),
                top_k=sizes["num_experts_per_tok"],
                scaling=float(sizes["routed_scaling_factor"]))
    scores, xs = [], []
    for i, what in enumerate(sizes["layer_types"]):
        x, biased, _ = ref.layer(x, p[f"layers_{i}"], what=what, **args)
        xs.append(np.asarray(x))
        if biased is not None:
            scores.append(np.asarray(biased))
    return np.asarray(ref.logits(params, sizes, seq)), np.stack(scores), \
        np.stack(xs)


def sets_differ(a, b):
    import numpy as np

    return (np.sort(a, -1) != np.sort(b, -1)).any(-1)


def main() -> int:
    from benchmarks.harness import serve_common

    family, config, mix = _cell()
    cases = os.environ.get("FOLLOW_CASES", "4200000401:155")
    for case in cases.split(","):
        seed, rid = (int(v) for v in case.split(":"))
        follow(family, config, mix, seed, rid,
               serve_common.LOGIT_TIE_TOLERANCE)
    return 0


def follow(family, config, mix, seed: int, rid: int, tol: float) -> None:
    import jax
    import numpy as np

    from benchmarks.harness import traffic
    from benchmarks.harness.replica import seeded_params
    from benchmarks.tools import lfm2_moe_faults as faults
    from ray_tpu.serve.llm_families import family_of

    ref = family.reference
    sizes = family.sizes(config)
    cfg = family.program_config(sizes)
    say(device=str(jax.devices()[0]), seed=seed, rid=rid,
        weights=family.WEIGHTS, routing_tie=ref.ROUTING_TIE)
    params = seeded_params(family.model(cfg), seed)
    req = traffic.serve_requests(mix, seed, sizes["vocab_size"], 45.0)[rid]
    prompt, P = list(req.prompt_tokens), len(req.prompt_tokens)
    engine = dict(config["serve"]["engine"])
    page, k = engine["page_size"], sizes["num_experts_per_tok"]
    (sample,), _ = faults.serve(cfg, params, engine, [prompt],
                                req.max_new_tokens)
    say(judged=faults.judged(family, params, sizes, engine["max_len"],
                             [sample]))
    if os.environ.get("FOLLOW_JUDGE_ONLY"):
        return
    got = list(sample["output"])
    seq = prompt + got[:-1]
    at = np.arange(len(got))

    lg, rscores, rxs = through_the_reference(ref, params, sizes, seq)
    lg = lg[P - 1:]
    own = lg.argmax(-1)
    gap = lg[at, own] - lg[at, np.asarray(got)]
    over = [int(i) for i in np.flatnonzero(gap > tol)]
    say(prompt=P, tokens=len(got), agree=int((gap == 0).sum()),
        over=[[i, float(gap[i])] for i in over])
    order = np.argsort(-rscores, axis=-1, kind="stable")
    rsel = order[..., :k]
    top = np.take_along_axis(rscores, order[..., : k + 1], -1)
    margin = top[..., k - 1] - top[..., k]              # (layers, S)

    fam = family_of(cfg, engine["max_len"])
    nlg, nscores, nsel, nxs = through_the_program(
        fam, params, prompt, got[:-1], page)
    ntok = nlg.argmax(-1)
    say(natural_tokens_are_the_engines=int((ntok == np.asarray(got)).sum()),
        first_other=[int(i) for i in np.flatnonzero(
            ntok != np.asarray(got))[:8]],
        top_k_is_exact=not bool(sets_differ(
            nsel, np.argsort(-nscores, -1, kind="stable")[..., :k]).any()))
    differ = sets_differ(nsel, rsel)                     # (layers, S)
    score_off = np.abs(nscores - rscores).max(-1)
    rel = lambda a, b: np.linalg.norm(a - b, axis=-1) / np.linalg.norm(  # noqa: E731
        b, axis=-1)
    stream_off = rel(nxs, rxs)                           # (all layers, S)
    dense = sizes["num_dense_layers"]
    rows = []
    for pos in np.flatnonzero(differ.any(0)):
        first = int(np.flatnonzero(differ[:, pos])[0])
        rows.append({
            "position": int(pos), "generated": int(pos - P + 1),
            "first_layer": first, "layers": int(differ[:, pos].sum()),
            "ref_margin": float(margin[first, pos]),
            "score_off": float(score_off[first, pos]),
            # what the router of that layer read: the stream after the
            # layer before it (its own operator is yet to come)
            "stream_off_before": float(stream_off[dense + first - 1, pos]),
            "near_tie": bool(margin[first, pos] < ref.ROUTING_TIE)})
    say(positions_whose_selection_differs=len(rows),
        of_them_not_near_a_tie=sum(not r["near_tie"] for r in rows),
        in_the_prompt=sum(r["generated"] <= 0 for r in rows))
    say(differing=[r for r in rows if r["generated"] > 0][:120])
    say(differing_not_near_a_tie=[r for r in rows if not r["near_tie"]][:60])
    for i in over:
        pos = P - 1 + i
        a, b = int(own[i]), int(got[i])
        say(over_at=i, position=pos, ref_a_minus_b=float(lg[i, a] - lg[i, b]),
            natural_a_minus_b=float(nlg[i, a] - nlg[i, b]),
            by_layer=[{
                "layer": j, "differs": bool(differ[j, pos]),
                "ref_margin": float(margin[j, pos]),
                "score_off": float(score_off[j, pos]),
                "stream_off_after": float(stream_off[dense + j, pos])}
                for j in range(differ.shape[0])],
            stream_off_after_dense=[float(v) for v in stream_off[:dense, pos]],
            neighbours=[{
                "position": int(q), "differs_in_layers": [
                    int(j) for j in np.flatnonzero(differ[:, q])],
                "least_margin": float(margin[:, q].min())}
                for q in range(max(0, pos - 6), pos)])

    forced = np.asarray(rsel, np.int32)
    olg, oscores, osel, oxs = through_the_program(
        fam, params, prompt, got[:-1], page, forced)
    pair = lambda m: [float(m[i, own[i]] - m[i, got[i]]) for i in over]  # noqa: E731
    say(oracle="the program with the reference's experts",
        logits_off_max=float(np.abs(olg - lg).max()),
        logits_off_rms=float(np.sqrt(np.mean((olg - lg) ** 2))),
        tokens_are_the_references=int((olg.argmax(-1) == own).sum()),
        of=len(got), a_minus_b_at_over=pair(olg), reference=pair(lg),
        score_off_max_by_layer=[float(v) for v in np.abs(
            oscores - rscores).max((1, 2))],
        score_off_rms_by_layer=[float(np.sqrt(np.mean(v ** 2)))
                                for v in oscores - rscores],
        stream_off_max_by_layer=[float(v) for v in rel(oxs, rxs).max(-1)])
    # With the reference's experts taken, how often would the program's own
    # scores have chosen otherwise, and at what margins: the noise alone.
    alone = sets_differ(np.argsort(-oscores, -1, kind="stable")[..., :k],
                        rsel)
    say(selections_the_noise_alone_moves=int(alone.sum()),
        of=int(alone.size), their_ref_margins=sorted(
            float(v) for v in margin[alone])[-12:])

    if over and os.environ.get("FOLLOW_CPU", "1") != "0":
        cpu = jax.devices("cpu")[0]
        host = jax.device_put(params, cpu)
        with jax.default_device(cpu):
            clg = np.asarray(ref.logits(host, sizes, seq,
                                        [P - 1 + i for i in over]))
        say(cpu_reference_a_minus_b=[
            float(clg[n, own[i]] - clg[n, got[i]])
            for n, i in enumerate(over)],
            cpu_logits_off_max=float(np.abs(clg - lg[over]).max()))


if __name__ == "__main__":
    sys.exit(main())
