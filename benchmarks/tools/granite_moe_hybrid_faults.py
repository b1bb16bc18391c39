"""Planted faults of the `granite_moe_hybrid` family, and what `correct`
makes of each: the ways ISSUE 52 names in which a program can get the
routed feed-forward, its share of a layer's experts, the attention scale or
a reused slot wrong, each a patch of `ray_tpu/models/granite_hybrid.py`,
`models/lfm2_moe.py` or the serving family that leaves everything else as
it is.  The CPU tests plant them at tiny sizes
(`tests/test_granite_moe_hybrid.py`); on the chip, at the published widths,

    chiprun --timeout 3000 -- env FAULTS_SEED=a,b,c python3 benchmarks/tools/granite_moe_hybrid_faults.py [fault ...]

serves the same prompts through a real `LLMEngine` under each fault, each
request decoding what a request of the cell may (256-768 tokens), and
judges the streams by the harness's own rule (`replica.check_reference`,
then `serve_common.judge`), one JSON line a fault and seed, the sound
engine's first, with each sample's own reading (`judged`).  The CONTROLS
are no faults but the nearest precisions below the stated ones:
`state_in_bf16` is the one `correct` has to refuse
(README-granite-moe-hybrid.md says why the one-term ones cannot be).
`levels` makes every level of the reference's roundings on the sound
engine's streams and counts what each would set aside; `probe` reads what
the configuration file states of the seeded weights (the reference's top
logit and margins, the router's margins and gates, how far the program's
router logits lie from the reference's, a layer at a time: ROUTING_TIE's
reason).  A line is 2-5 minutes on the chip: name what you want.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tools.lfm2_moe_faults import (_bf16_router,  # noqa: E402
                                              _one_term)

CELL = "granite4hs-serve-desk-closed"


def _granite():
    from ray_tpu.models import granite_hybrid

    return granite_hybrid


def _routed():
    from ray_tpu.models import lfm2_moe

    return lfm2_moe


def _serving():
    from ray_tpu.serve.llm_families import GraniteHybridServing

    return GraniteHybridServing


def _route_with(**how):
    """A `route` that departs from the sound one as `how` says."""
    import jax
    import jax.numpy as jnp

    def route(logits, top_k):
        keep = top_k - 1 if how.get("one_fewer") else top_k
        top, idx = jax.lax.top_k(logits.astype(jnp.float32), keep)
        gates = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx,
                                    axis=-1) if how.get("over_all") \
            else jax.nn.softmax(top, axis=-1)
        if keep < top_k:        # the pair that is not computed: gate 0
            idx = jnp.concatenate([idx, idx[:, :1]], axis=-1)
            gates = jnp.concatenate(
                [gates, jnp.zeros_like(gates[:, :1])], axis=-1)
        return idx, gates

    return route


def _share_ignored(sound):
    """Every pair is computed and weighed, an absent expert's by the held
    expert that lies where it would (expert e by e - first mod count): what
    a layer does that was never told what it holds."""
    def expert_ffn(u, idx, gates, w13, w2, valid=None, first=None):
        return sound(u, (idx - (first or 0)) % w13.shape[0], gates, w13, w2,
                     valid)

    return expert_ffn


def _no_shared_expert(sound):
    import jax.numpy as jnp

    def call(self, x):
        out = sound(self, x)
        return jnp.zeros_like(out) if self.cfg.n_experts else out

    return call


def _stale_state(sound):
    """A reused slot's Mamba-2 state is added to, not replaced."""
    def write_prompt(self, state, fresh, slots, page_ids):
        out = sound(self, state, fresh, slots, page_ids)
        return dict(out, ssm=[
            (conv, old_s.at[slots].add(new_s, mode="drop"))
            for (conv, _), (_, old_s), (_, new_s)
            in zip(out["ssm"], state["ssm"], fresh["ssm"])])

    return write_prompt


# name -> (what is patched, its attribute, sound -> faulty)
FAULTS = {
    "i_share_ignored_every_pair_computed": (_routed, "expert_ffn",
                                            _share_ignored),
    "ii_gates_a_softmax_over_all_72_logits": (
        _granite, "route", lambda sound: _route_with(over_all=True)),
    "iii_top_9": (_granite, "route",
                  lambda sound: _route_with(one_fewer=True)),
    "iv_shared_expert_left_out": (lambda: _granite().MLP, "__call__",
                                  _no_shared_expert),
    "vi_reused_slot_keeps_its_state": (_serving, "write_prompt",
                                       _stale_state),
}
# Faults of the configuration, not of a function.
CONFIG_FAULTS = {
    "v_attention_multiplier_1_64": dict(attention_multiplier=1 / 64),
}
def _bf16_state(sound):
    """The Mamba-2 state S kept to bfloat16's bits after every decode step
    (rounded to nearest on the value's bits: the chip's compiler elides a
    convert there and back)."""
    import jax
    import jax.numpy as jnp

    def state_step(s_prev, decay, drive, b_sel, c_sel):
        y, s = sound(s_prev, decay, drive, b_sel, c_sel)
        bits = jax.lax.bitcast_convert_type(s, jnp.uint32)
        bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
            & jnp.uint32(0xFFFF0000)
        return y, jax.lax.bitcast_convert_type(bits, jnp.float32)

    return state_step


def _sambay():
    from ray_tpu.models import sambay

    return sambay


# Not faults of the issue's list: controls of the tolerance, each the
# nearest precision below one that the configuration states.  The grouped
# products take their rows in ONE bfloat16 term (a plain bf16 program's
# experts; the router and every other product stay as they are); EVERY
# product with a weight does (a plain bf16 program but for its float32
# stream, router and state); the state S is kept in bfloat16.
CONTROLS = {
    "experts_in_one_bf16_term": [(_routed, "_two_terms", _one_term)],
    "every_product_in_one_bf16_term": [(_routed, "_two_terms", _one_term),
                                       (_sambay, "_two_terms", _one_term)],
    "state_in_bf16": [(_granite, "state_step", _bf16_state)],
    "router_in_one_bf16_term": [(_routed, "router_logits", _bf16_router)],
}


@contextlib.contextmanager
def planted(name: str | None):
    """The program with fault `name` in it (None, or a fault of the
    configuration: the sound program)."""
    if name is None or name in CONFIG_FAULTS:
        yield
        return
    patches = CONTROLS[name] if name in CONTROLS else [FAULTS[name]]
    with contextlib.ExitStack() as stack:
        for where, attr, make in patches:
            target = where()
            stack.enter_context(mock.patch.object(
                target, attr, make(getattr(target, attr))))
        yield


def serve(cfg, params, engine: dict, prompts: list, new_tokens: list,
          fault: str | None = None) -> tuple:
    """`prompts` through a fresh `LLMEngine` (all submitted at once: more
    of them than slots, so that slots are reused), `new_tokens[i]` tokens
    for prompt i -> the harness's samples, and what the engine counted."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.serve.llm import LLMEngine

    cfg = dataclasses.replace(cfg, **CONFIG_FAULTS.get(fault, {}))
    with planted(fault):
        eng = LLMEngine(cfg, params, **engine)
        try:
            eng.quiesce_for_drain()
            handles = [eng.submit(p, SamplingParams(max_new_tokens=int(n)))
                       for p, n in zip(prompts, new_tokens)]
            eng.resume()
            outs = [h.tokens() for h in handles]
            counted = {k: v for k, v in eng.report_metrics().items()
                       if k.startswith("expert")}
        finally:
            eng.shutdown()
    return [{"rid": i, "prompt": list(p), "output": list(o)}
            for i, (p, o) in enumerate(zip(prompts, outs))], counted


# ---------------------------------------------------------------------------
# What the seeded weights give (the configuration file's `assumed.weights`)
# ---------------------------------------------------------------------------


def program_logits(cfg, params, tokens, decode_from: int) -> list:
    """The program's router logits, a layer: (S, E) over `tokens`,
    positions before `decode_from` by one prefill and the rest by decode
    steps through pages and state (one sequence, run eagerly, `route` made
    to tell what it was handed)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_families import family_of

    mod = _granite()
    seen: list = []
    sound = mod.route

    def telling(logits, top_k):
        seen.append(np.asarray(logits))
        return sound(logits, top_k)

    page = 64
    n_pages = -(-len(tokens) // page) + 1
    fam = family_of(cfg, n_pages * page)
    toks = jnp.asarray(tokens, jnp.int32)
    with mock.patch.object(mod, "route", telling):
        padded = jnp.zeros((1, -(-decode_from // page) * page),
                           jnp.int32).at[0, :decode_from].set(
                               toks[:decode_from])
        _, fresh, _ = fam.prefill(params, padded,
                                  jnp.asarray([decode_from - 1]))
        layers = len(seen)
        rows = [s[:decode_from] for s in seen]
        state = fam.init_state(1, n_pages + 1, page)
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        state = fam.write_prompt(state, fresh, jnp.asarray([0]),
                                 table[:, : padded.shape[1] // page])
        del seen[:]
        for t in range(decode_from, len(tokens)):
            at = jnp.asarray([t], jnp.int32)
            _, state, _ = fam.decode(params, toks[t: t + 1], at, state,
                                     table, at, jnp.asarray([True]))
    for i in range(layers):
        rows[i] = np.concatenate([rows[i]] + seen[i::layers], axis=0)
    return rows


def probe(family, cfg, params, sizes: dict, tokens: list,
          decode_from: int) -> dict:
    """Over one sequence of random ids: the reference's logits (top, top-2
    margin, how often its best token is the one just read), its router's
    logits, gates and 10th-11th margins (all, and those of which one of
    the two experts is held: the ones an exchange matters at), and how far
    the program's router logits lie from the reference's, a layer at a
    time."""
    import numpy as np

    ref = family.reference
    scores: list = []
    ref.hidden_states(params, sizes, tokens, scores=scores)
    scores = [np.asarray(s) for s in scores]
    k = sizes["num_experts_per_tok"]
    first, count = ref.held_experts(sizes)
    out = {"positions": len(tokens), "decode_from": decode_from,
           "router_logit_std": float(np.mean([s.std() for s in scores]))}
    margins, matter, gates = [], [], []
    for s in scores:
        order = np.argsort(-s, axis=-1)
        top = np.take_along_axis(s, order, -1)
        margins.append(top[:, k - 1] - top[:, k])
        pair = order[:, k - 1: k + 1]
        matter.append(((pair >= first) & (pair < first + count)).any(-1))
        e = np.exp(top[:, :k] - top[:, :1])
        gates.append(e / e.sum(-1, keepdims=True))
    margins, matter = np.concatenate(margins), np.concatenate(matter)
    gates = np.concatenate(gates)
    out["gate_largest_mean"] = float(gates[:, 0].mean())
    out["gate_tenth_mean"] = float(gates[:, -1].mean())
    out["router_margin_median"] = float(np.median(margins))
    out["router_margin_p01"] = float(np.quantile(margins, 0.01))
    out["ties_share_of_selections"] = float(np.mean(
        (margins < ref.ROUTING_TIE) & matter))
    per_position = (np.stack(np.split(
        (margins < ref.ROUTING_TIE) & matter, len(scores))).any(0))
    out["positions_with_a_tie_share"] = float(per_position.mean())
    out["held_share_of_pairs"] = float(np.mean([
        ((o >= first) & (o < first + count)).mean()
        for o in (np.argsort(-s, -1)[:, :k] for s in scores)]))
    mine = program_logits(cfg, params, tokens, decode_from)
    diff = [np.abs(a - b) for a, b in zip(mine, scores)]
    q = lambda d, p: float(np.quantile(d, p))  # noqa: E731
    out["router_logit_diff_by_layer"] = [
        {"rms": float(np.sqrt(np.mean(d ** 2))), "p99": q(d, 0.99),
         "p999": q(d, 0.999), "max": float(d.max()),
         "decode_p999": q(d[decode_from:], 0.999)} for d in diff]
    out["selections_that_differ_share_by_layer"] = [
        float(np.mean((np.sort(np.argsort(-a, -1)[:, :k], -1)
                       != np.sort(np.argsort(-b, -1)[:, :k], -1)).any(-1)))
        for a, b in zip(mine, scores)]
    # the widest margin at which the program took another set than the
    # reference, a layer (0: none differs)
    widest = []
    for a, b in zip(mine, scores):
        differs = (np.sort(np.argsort(-a, -1)[:, :k], -1)
                   != np.sort(np.argsort(-b, -1)[:, :k], -1)).any(-1)
        top = -np.sort(-b, axis=-1)
        widest.append(float((top[:, k - 1] - top[:, k])[differs].max(
            initial=0.0)))
    out["widest_margin_exchanged_by_layer"] = widest
    lg = np.asarray(ref.logits(params, sizes, tokens))
    top2 = np.partition(lg, -2, axis=-1)[:, -2:]
    out["logit_std"] = float(lg.std())
    out["top_logit_mean"] = float(top2[:, 1].mean())
    out["top2_margin_median"] = float(np.median(top2[:, 1] - top2[:, 0]))
    out["greedy_repeats_its_input_share"] = float(
        np.mean(lg.argmax(-1) == np.asarray(tokens)))
    return out


def levels(family, params, sizes: dict, max_len: int, samples: list) -> dict:
    """Every level of the reference's ROUNDINGS over `samples`, whatever
    their gaps (a run of the cell makes them only on a sample with a gap
    over the tolerance): the positions each level alone would set aside,
    and all of them together, against the harness's cap."""
    import types

    import numpy as np

    from benchmarks.harness import serve_common
    from benchmarks.harness.replica import BenchLLM

    replica = object.__new__(BenchLLM)
    replica._family, replica._params, replica._sizes = family, params, sizes
    replica.engine = types.SimpleNamespace(max_len=max_len)
    tol = serve_common.LOGIT_TIE_TOLERANCE
    by_level = np.zeros(len(family.reference.ROUNDINGS) - 1, int)
    positions = aside_all = worst_share = 0
    for s in samples:
        prompt, got = s["prompt"], s["output"]
        at = np.arange(len(got))
        own = replica.reference_logits(prompt, got).argmax(-1)
        moved = np.stack([
            (lambda p: p.max(-1) - p[at, own])(
                replica.reference_logits(prompt, got, rounded=level))
            for level in range(1, len(family.reference.ROUNDINGS))])
        aside = (moved > tol).any(0)
        by_level += (moved > tol).sum(1)
        positions += len(got)
        aside_all += int(aside.sum())
        worst_share = max(worst_share, float(aside.mean()))
    return {"positions": positions, "set_aside_by_level": by_level.tolist(),
            "set_aside": aside_all, "set_aside_share": aside_all / positions,
            "widest_share_of_a_sample": worst_share,
            "cap": serve_common.SET_ASIDE_SHARE}


# Four slots and nine prompts (every slot reused), rows of unequal length
# in one padded bucket of each of two sizes, as
# `tests/test_families_served.py` has them at tiny sizes; each request
# decodes as many tokens as a request of the cell may (`desk-closed`:
# uniform 256-768), as far as the engine's 1,280 positions hold them: what
# a lower precision of the STATE leaves grows with every decode step, and
# `correct` judges streams of that length.
ENGINE = dict(max_batch=4, max_len=1280, page_size=64, decode_chunk=8)
PROMPTS = (300, 700, 520, 640, 270, 600, 430, 480, 512)
NEW_TOKENS = (256, 768)


def judged(family, params, sizes: dict, samples: list) -> dict:
    """The samples by `replica.check_reference` and `serve_common.judge`,
    as a run of the cell judges its own (`tools/lfm2_moe_faults.judged`'s
    line), and each sample's [tokens, widest gap, widest gap kept]: a state
    kept too short reads the wider the longer the stream."""
    import types

    from benchmarks.harness import serve_common
    from benchmarks.harness.replica import BenchLLM

    replica = object.__new__(BenchLLM)
    replica._family, replica._params, replica._sizes = family, params, sizes
    replica.engine = types.SimpleNamespace(max_len=ENGINE["max_len"])
    compared = replica.check_reference(
        samples, serve_common.LOGIT_TIE_TOLERANCE)
    problems = [p for p in map(serve_common.judge, compared) if p]
    tokens = sum(c["tokens"] for c in compared)
    kept = lambda c: [o for o in c["over"]  # noqa: E731
                      if o[0] not in (c.get("set_aside_at") or ())]
    return {"refused": bool(problems), "problems": problems[:2],
            "widest_gap": max(c["max_logit_gap"] for c in compared),
            "widest_gap_kept": max(c["kept_max_gap"] for c in compared),
            "positions": tokens,
            "positions_over": sum(len(c["over"]) for c in compared),
            "positions_over_and_kept": sum(len(kept(c)) for c in compared),
            "samples_refused": len(problems),
            "set_aside": sum(c["set_aside"] or 0 for c in compared),
            "mean_top_logit": sum(c["mean_top_logit"] * c["tokens"]
                                  for c in compared) / tokens,
            "median_top2_margin": sorted(
                c["median_top2_margin"] for c in compared)[len(compared) // 2],
            "by_sample": [[c["tokens"], round(c["max_logit_gap"], 4),
                           round(c["kept_max_gap"], 4)] for c in compared]}


def one_seed(cell, seed: int, argv: list) -> None:
    import time

    import jax
    import numpy as np

    from benchmarks.harness.replica import seeded_params

    family, sizes = cell.family, cell.family.sizes(cell.config)
    cfg = family.program_config(sizes)
    params = seeded_params(family.model(cfg), seed)
    print(json.dumps({"device": str(jax.devices()[0]), "seed": seed,
                      "weights": family.WEIGHTS,
                      "routing_tie": family.reference.ROUTING_TIE}),
          flush=True)
    rng = np.random.default_rng(seed)
    V = sizes["vocab_size"]
    if "probe" in argv or not argv:
        tokens = rng.integers(0, V, size=1024).tolist()
        print(json.dumps({"probe": probe(family, cfg, params, sizes, tokens,
                                         896)}), flush=True)
    prompts = [rng.integers(0, V, size=n).tolist() for n in PROMPTS]
    new_tokens = [min(int(n), ENGINE["max_len"] - len(p)) for n, p in zip(
        rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1, size=len(prompts)),
        prompts)]
    names = [a for a in argv if a != "probe"] or [
        "sound", *CONTROLS, *FAULTS, *CONFIG_FAULTS]
    for name in names:
        t0 = time.time()
        if name == "levels":    # the sound engine's streams, every level
            samples, _ = serve(cfg, params, ENGINE, prompts, new_tokens)
            print(json.dumps({"levels": levels(
                family, params, sizes, ENGINE["max_len"], samples[:4]),
                "seed": seed, "seconds": round(time.time() - t0)}),
                flush=True)
            continue
        samples, counted = serve(cfg, params, ENGINE, prompts, new_tokens,
                                 None if name == "sound" else name)
        served = time.time() - t0
        line = judged(family, params, sizes, samples)
        print(json.dumps({"fault": name, "seed": seed, **line,
                          "counted": counted,
                          "seconds": [round(served),
                                      round(time.time() - t0)]}),
              flush=True)


def main(argv) -> int:
    from benchmarks.harness import loader

    cell = loader.load_cell(CELL)
    for seed in os.environ.get("FAULTS_SEED", "4200000521").split(","):
        one_seed(cell, int(seed), argv)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
