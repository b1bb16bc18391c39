"""Planted faults of the `lfm2_moe` family, and what `correct` makes of
each: the eleven ways ISSUE 42 names in which a program can get the routed
layer, the conv window or the head norms wrong, each a patch of
`ray_tpu/models/lfm2_moe.py` or of its serving family that leaves
everything else as it is.  The CPU tests plant them at tiny sizes
(`tests/test_lfm2_moe.py`); on the chip, at the published widths,

    chiprun --timeout 3000 -- python3 benchmarks/tools/lfm2_moe_faults.py [fault ...]

serves the same prompts through a real `LLMEngine` under each fault and
judges the streams by the harness's own rule (`replica.check_reference`,
then `serve_common.judge`), one JSON line a fault, the sound engine's first
(`experts_in_one_bf16_term`, a control and no fault, only where named);
`probe` before them reads what the configuration file states of the seeded
weights (the reference's top logit and margins, how often the selection
bias changes the chosen set, how far the program's router scores lie from
the reference's: ROUTING_TIE's reason).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _route_with(**how):
    """A `route` that departs from the sound one as `how` says."""
    import jax
    import jax.numpy as jnp

    def route(logits, bias, top_k):
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        biased = s + (0.0 if how.get("no_bias") else bias)
        keep = top_k - 1 if how.get("one_fewer") else top_k
        top, idx = jax.lax.top_k(biased, keep)
        chosen = top if how.get("biased_gates") \
            else jnp.take_along_axis(s, idx, axis=-1)
        gates = chosen if how.get("no_renorm") \
            else chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
        if keep < top_k:        # the pair that is not computed: gate 0
            idx = jnp.concatenate([idx, idx[:, :1]], axis=-1)
            gates = jnp.concatenate(
                [gates, jnp.zeros_like(gates[:, :1])], axis=-1)
        if how.get("capacity"):
            # pairs past an expert's capacity, in token order, are dropped
            E = logits.shape[-1]
            taken = jax.nn.one_hot(idx.reshape(-1), E, dtype=jnp.int32)
            place = jnp.take_along_axis(
                jnp.cumsum(taken, axis=0), idx.reshape(-1, 1), axis=1)
            cap = -(-int(how["capacity"] * idx.size) // E)
            gates = jnp.where(place.reshape(idx.shape) <= cap, gates, 0.0)
        return idx, gates

    return route


def _module():
    from ray_tpu.models import lfm2_moe

    return lfm2_moe


def _serving():
    from ray_tpu.serve.llm_families import Lfm2MoeServing

    return Lfm2MoeServing


def _w2_exchanged(sound):
    import jax.numpy as jnp

    def expert_ffn(u, idx, gates, w13, w2, valid=None):
        swap = jnp.arange(w2.shape[0]).at[:2].set(jnp.array([1, 0]))
        return sound(u, idx, gates, w13, w2[swap], valid)

    return expert_ffn


def _write_prompt(conv):
    """A `write_prompt` whose conv windows are `conv(old, new, slots)`."""
    def make(sound):
        def write_prompt(self, state, fresh, slots, page_ids):
            out = sound(self, state, fresh, slots, page_ids)
            return dict(out, conv=[
                conv(old, new, slots)
                for old, new in zip(state["conv"], fresh["conv"])])

        return write_prompt

    return make


def _no_head_norm(sound):
    class NoHeadNorm(sound):
        def __call__(self, x):          # (the scale is still declared)
            out = sound.__call__(self, x)
            return x if x.ndim == 4 else out    # (B, S, heads, 64)

    return NoHeadNorm


def _bf16_router(sound):
    import jax.numpy as jnp

    return lambda u, w: jnp.dot(u.astype(jnp.bfloat16),
                                w.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)


# name -> (what is patched, its attribute, sound -> faulty)
FAULTS = {
    "i_bias_ignored": (_module, "route",
                       lambda sound: _route_with(no_bias=True)),
    "ii_gates_from_biased_scores": (
        _module, "route", lambda sound: _route_with(biased_gates=True)),
    "iii_no_renormalisation": (
        _module, "route", lambda sound: _route_with(no_renorm=True)),
    "iv_top_k_less_one": (_module, "route",
                          lambda sound: _route_with(one_fewer=True)),
    "v_capacity_1.25_dropped": (_module, "route",
                                lambda sound: _route_with(capacity=1.25)),
    "vi_w2_of_two_experts_exchanged": (_module, "expert_ffn", _w2_exchanged),
    "vii_conv_window_not_carried": (
        _serving, "write_prompt", _write_prompt(
            lambda old, new, slots: old.at[slots].set(0 * new, mode="drop"))),
    "viii_reused_slot_keeps_its_window": (
        _serving, "write_prompt", _write_prompt(
            lambda old, new, slots: old.at[slots].add(new, mode="drop"))),
    "ix_padding_leaks_into_the_window": (
        lambda: _module().ShortConv, "__call__",
        lambda sound: lambda self, u, last_idx=None: sound(self, u, None)),
    "x_no_head_norms": (_module, "RMSNorm", _no_head_norm),
    "xi_router_in_one_bf16_term": (_module, "router_logits", _bf16_router),
}


def _one_term(sound):
    import jax.numpy as jnp

    def one_term(a, axis):
        a = a.astype(jnp.bfloat16)
        return jnp.concatenate([a, jnp.zeros_like(a)], axis=axis)

    return one_term


# Not a fault of ISSUE 42's list: a control of the tolerance.  The grouped
# products take their rows in ONE bfloat16 term (a plain bf16 program's
# experts), the router and every other product stay as they are.
CONTROLS = {
    "experts_in_one_bf16_term": (_module, "_two_terms", _one_term),
}


@contextlib.contextmanager
def planted(name: str | None):
    """The program with fault `name` in it (None: the sound program)."""
    if name is None:
        yield
        return
    where, attr, make = {**FAULTS, **CONTROLS}[name]
    target = where()
    with mock.patch.object(target, attr, make(getattr(target, attr))):
        yield


# ---------------------------------------------------------------------------
# Through the engine and the harness's rule
# ---------------------------------------------------------------------------


def serve(cfg, params, engine: dict, prompts: list, new_tokens: int,
          fault: str | None = None) -> list:
    """`prompts` through a fresh `LLMEngine` (all submitted at once: more
    of them than slots, so that slots are reused) -> the harness's samples."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.serve.llm import LLMEngine

    with planted(fault):
        eng = LLMEngine(cfg, params, **engine)
        try:
            eng.quiesce_for_drain()
            handles = [eng.submit(p, SamplingParams(max_new_tokens=new_tokens))
                       for p in prompts]
            eng.resume()
            outs = [h.tokens() for h in handles]
            counted = {k: v for k, v in eng.report_metrics().items()
                       if k.startswith("expert")}
        finally:
            eng.shutdown()
    return [{"rid": i, "prompt": list(p), "output": list(o)}
            for i, (p, o) in enumerate(zip(prompts, outs))], counted


def judged(family, params, sizes: dict, max_len: int, samples: list) -> dict:
    """The samples by `replica.check_reference` and `serve_common.judge`,
    as a run of the cell judges its own."""
    import types

    from benchmarks.harness import serve_common
    from benchmarks.harness.replica import BenchLLM

    replica = object.__new__(BenchLLM)
    replica._family, replica._params, replica._sizes = family, params, sizes
    replica.engine = types.SimpleNamespace(max_len=max_len)
    compared = replica.check_reference(
        samples, serve_common.LOGIT_TIE_TOLERANCE)
    problems = [p for p in map(serve_common.judge, compared) if p]
    tokens = sum(c["tokens"] for c in compared)
    return {"refused": bool(problems), "problems": problems[:2],
            "widest_gap": max(c["max_logit_gap"] for c in compared),
            "widest_gap_kept": max(c["kept_max_gap"] for c in compared),
            "positions": tokens,
            "positions_over": sum(len(c["over"]) for c in compared),
            "positions_over_and_kept": sum(
                o[0] not in (c.get("set_aside_at") or ())
                for c in compared for o in c["over"]),
            "set_aside": sum(c["set_aside"] or 0 for c in compared),
            "set_aside_by_pass": [c.get("set_aside_by_pass")
                                  for c in compared if c["over"]][:4],
            "set_aside_at": [c.get("set_aside_at")
                             for c in compared if c["over"]][:4],
            "mean_top_logit": sum(c["mean_top_logit"] * c["tokens"]
                                  for c in compared) / tokens,
            "median_top2_margin": sorted(
                c["median_top2_margin"] for c in compared)[len(compared) // 2]}


# ---------------------------------------------------------------------------
# What the seeded weights give (the configuration file's `assumed.weights`)
# ---------------------------------------------------------------------------


def program_scores(cfg, params, tokens, decode_from: int) -> list:
    """The program's biased router scores, a routed layer: (S, E) over
    `tokens`, positions before `decode_from` by one prefill and the rest by
    decode steps through pages and conv windows (one sequence, run
    eagerly, `route` made to tell what it was handed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_families import family_of

    mod = _module()
    seen: list = []
    sound = mod.route

    def telling(logits, bias, top_k):
        seen.append(np.asarray(jax.nn.sigmoid(logits) + bias))
        return sound(logits, bias, top_k)

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
    margins and how often the bias changes the chosen set, and the widest
    difference between the program's router scores and the reference's."""
    import numpy as np

    ref = family.reference
    scores: list = []
    ref.hidden_states(params, sizes, tokens, scores=scores)
    scores = [np.asarray(s) for s in scores]
    k = sizes["num_experts_per_tok"]
    out = {"positions": len(tokens), "decode_from": decode_from}
    bias = [np.asarray(params["params"][f"layers_{i}"]["experts"][
        "expert_bias"]) for i in range(len(sizes["layer_types"]))
        if i >= sizes["num_dense_layers"]]
    margins, changed = [], []
    for s, b in zip(scores, bias):
        top = -np.sort(-s, axis=-1)
        margins.append(top[:, k - 1] - top[:, k])
        with_bias = np.argsort(-s, axis=-1)[:, :k]
        without = np.argsort(-(s - b), axis=-1)[:, :k]
        changed.append(float(np.mean(
            (np.sort(with_bias, -1) != np.sort(without, -1)).any(-1))))
    margins = np.concatenate(margins)
    out["router_margin_median"] = float(np.median(margins))
    out["router_margin_p01"] = float(np.quantile(margins, 0.01))
    out["router_margin_under_tie_share"] = float(
        np.mean(margins < ref.ROUTING_TIE))
    out["bias_changes_the_set_share"] = float(np.mean(changed))
    out["experts_chosen_by_16_rows"] = float(np.mean([
        len(np.unique(np.argsort(-s[i: i + 16], -1)[:, :k]))
        for s in scores for i in range(0, len(s) - 16, 16)]))
    mine = program_scores(cfg, params, tokens, decode_from)
    diff = [np.abs(a - b) for a, b in zip(mine, scores)]
    # The first routed layer's router reads a stream no other router has
    # touched: what the program's precision alone puts on a score.  (In a
    # later layer a token whose earlier selection differed reads far off.)
    first = diff[0]
    rms = lambda d: float(np.sqrt(np.mean(d ** 2)))  # noqa: E731
    out["first_router_score_diff"] = {
        "prefill_rms": rms(first[:decode_from]),
        "prefill_max": float(first[:decode_from].max()),
        "decode_rms": rms(first[decode_from:]),
        "decode_max": float(first[decode_from:].max())}
    out["router_score_diff_max_by_layer"] = [float(d.max()) for d in diff]
    out["selections_that_differ_share_by_layer"] = [
        float(np.mean((np.sort(np.argsort(-a, -1)[:, :k], -1)
                       != np.sort(np.argsort(-b, -1)[:, :k], -1)).any(-1)))
        for a, b in zip(mine, scores)]
    lg = np.asarray(ref.logits(params, sizes, tokens))
    top2 = np.partition(lg, -2, axis=-1)[:, -2:]
    out["logit_std"] = float(lg.std())
    out["top_logit_mean"] = float(top2[:, 1].mean())
    out["top2_margin_median"] = float(np.median(top2[:, 1] - top2[:, 0]))
    out["greedy_repeats_its_input_share"] = float(
        np.mean(lg.argmax(-1) == np.asarray(tokens)))
    return out


def main(argv) -> int:
    import jax
    import numpy as np

    from benchmarks.harness import loader
    from benchmarks.harness.replica import seeded_params

    cell = loader.load_cell("lfm2moe-serve-agents-closed")
    family, sizes = cell.family, cell.family.sizes(cell.config)
    cfg = family.program_config(sizes)
    seed = int(os.environ.get("FAULTS_SEED", 4200000101))
    params = seeded_params(family.model(cfg), seed)
    print(json.dumps({"device": str(jax.devices()[0]), "seed": seed,
                      "weights": family.WEIGHTS}), flush=True)
    rng = np.random.default_rng(seed)
    V = sizes["vocab_size"]
    if "probe" in argv or not argv:
        tokens = rng.integers(0, V, size=1024).tolist()
        print(json.dumps({"probe": probe(family, cfg, params, sizes, tokens,
                                         896)}), flush=True)
    # Four slots and nine prompts (every slot reused), rows of unequal
    # length in one padded bucket of each of two sizes, as
    # `tests/test_lfm2_moe.py` has them at tiny sizes.
    engine = dict(max_batch=4, max_len=1280, page_size=64, decode_chunk=8)
    prompts = [rng.integers(0, V, size=n).tolist()
               for n in (300, 1000, 520, 640, 270, 900, 430, 777, 512)]
    names = [a for a in argv if a != "probe"] or ["sound", *FAULTS]
    for name in names:
        samples, counted = serve(cfg, params, engine, prompts, 160,
                                 None if name == "sound" else name)
        print(json.dumps({"fault": name, **judged(
            family, params, sizes, engine["max_len"], samples),
            "counted": counted}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
