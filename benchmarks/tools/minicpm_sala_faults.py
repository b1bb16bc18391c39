"""Planted faults of the `minicpm_sala` family, and what `correct` makes of
each: the ways ISSUE 49 names in which a program can get block-sparse
attention or the lightning layers wrong, each a change of the program's
configuration or a patch of `ray_tpu/models/minicpm_sala.py` that leaves
everything else as it is.  The CPU tests plant them at tiny sizes
(`tests/test_minicpm_sala.py`); on the chip, at the published widths,

    chiprun --timeout 3000 -- python3 benchmarks/tools/minicpm_sala_faults.py [probe] [selection] [fault ...]

serves the same prompts (one under `dense_len`, one that crosses it while
it decodes, two past it) through a real `LLMEngine` under each fault and
judges the streams by the harness's own rule (`replica.check_reference`,
then `serve_common.judge`: `tools/lfm2_moe_faults.judged`), one JSON line a
fault, the sound engine's first; `probe` before them reads what the
configuration file states of the seeded weights: the reference's top logit
and margins, each mixer's share of what is added to the stream, how sharp
the sparse layers' softmax is; `selection` how far the program's selection
scores lie from the reference's served pass over a prompt past `dense_len`,
and at which margins between the last kept and the first dropped block
their kept sets differ (SELECTION_TIE's reason).  `FAULTS_WEIGHTS` (a
JSON object) replaces deviations of `families/minicpm_sala.WEIGHTS` for the
run: how the committed values were chosen.
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


def _module():
    from ray_tpu.models import minicpm_sala

    return minicpm_sala


def _serving():
    from ray_tpu.serve import llm_families

    return llm_families.MiniCpmSalaServing


def _keys_never_extended(sound):
    def decode(self, params, token, pos, state, tables, lens, live):
        logits, new, counts = sound(self, params, token, pos, state, tables,
                                    lens, live)
        return logits, dict(new, cpools=state["cpools"]), counts

    return decode


def _decays_reversed(sound):
    return lambda self: sound(self)[::-1]


def _no_gate(sound):
    import jax.numpy as jnp

    def gated_out(x, o, g, p, c):
        # sigmoid(g) = 1: g far up
        return sound(x, o, jnp.full_like(g, 1e4), p, c)

    return gated_out


# name -> (the program's configuration changed, or None;
#          [(what is patched, its attribute, sound -> faulty), ...])
FAULTS = {
    "i_dense_where_sparse_is_due": (
        lambda c: dataclasses.replace(c, dense_len=2 * c.dense_len), []),
    "ii_top_k_one_short": (
        lambda c: dataclasses.replace(c, topk=c.topk - 1), []),
    "iii_windows_blocks_dropped": (
        lambda c: dataclasses.replace(c, window_size=c.block_size), []),
    "iv_compressed_keys_never_extended": (
        None, [(_serving, "decode", _keys_never_extended)]),
    "v_decay_of_the_wrong_head": (
        None, [(lambda: _module().MiniCpmSalaConfig, "decays",
                _decays_reversed)]),
    "vi_output_gate_left_out": (
        None, [(_module, "_gated_out", _no_gate)]),
    "vii_scale_depth_over_the_root_of_the_layers_held": (
        lambda c: dataclasses.replace(c, depth_layers=c.n_layers), []),
}


# What is served long (`main`): the fault that only a long answer shows,
# and its control.
LONG = ("iv_compressed_keys_never_extended", "sound_long")


@contextlib.contextmanager
def planted(name: str | None, cfg):
    """The program with fault `name` in it (None: the sound program):
    yields the configuration to build it from."""
    change, patches = FAULTS[name] if name else (None, [])
    with contextlib.ExitStack() as stack:
        for where, attr, make in patches:
            target = where()
            stack.enter_context(mock.patch.object(
                target, attr, make(getattr(target, attr))))
        yield change(cfg) if change else cfg


def serve(cfg, params, engine: dict, prompts: list, new_tokens: int,
          fault: str | None = None) -> tuple:
    """`prompts` through a fresh `LLMEngine` (all submitted at once: more
    of them than slots, so that a slot is reused) -> the harness's samples
    and what the family's programs counted."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.serve.llm import LLMEngine

    with planted(fault, cfg) as faulty:
        eng = LLMEngine(faulty, params, **engine)
        try:
            eng.quiesce_for_drain()
            handles = [eng.submit(p, SamplingParams(
                max_new_tokens=new_tokens)) for p in prompts]
            eng.resume()
            outs = [h.tokens() for h in handles]
            counted = {k: v for k, v in eng.report_metrics().items()
                       if k.startswith(("sparse", "compressed"))}
        finally:
            eng.shutdown()
    return [{"rid": i, "prompt": list(p), "output": list(o)}
            for i, (p, o) in enumerate(zip(prompts, outs))], counted


def program_selection(cfg, params, tokens: list) -> list:
    """The program's selection scores over `tokens` as ONE prompt, a sparse
    layer: (S, Hkv, blocks), a block's score where it competed for a
    query's top-k and -1 elsewhere (run eagerly, `rank_blocks` made to tell
    what it returned)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mod = _module()
    seen: list = []
    sound = mod.rank_blocks

    def telling(q, ck, qpos, c):
        cand, forced = sound(q, ck, qpos, c)
        if not isinstance(cand, jax.core.Tracer):   # (not a shape's trace)
            seen.append((np.asarray(qpos[0]), np.asarray(cand[0])))
        return cand, forced

    S = len(tokens)
    blocks = -(-S // cfg.block_size)
    with mock.patch.object(mod, "rank_blocks", telling), jax.disable_jit():
        mod.MiniCpmSalaModel(cfg).apply(
            params, jnp.asarray(tokens, jnp.int32)[None],
            jnp.asarray([S - 1]), method=mod.MiniCpmSalaModel.prefill)
    layers = [np.full((S, cfg.n_kv_heads, blocks), -1.0, np.float32)
              for _ in cfg.layers_of(mod.SPARSE)]
    turn = {}
    for qpos, cand in seen:     # (a layer's query blocks come in order)
        at = turn[int(qpos[0])] = turn.get(int(qpos[0]), -1) + 1
        real = qpos < S
        layers[at][qpos[real], :, : cand.shape[-1]] = \
            cand.transpose(1, 0, 2)[real]
    return layers


def selection_probe(family, cfg, params, sizes: dict, tokens: list) -> dict:
    """How far the program's selection scores lie from the reference's
    served pass over one prompt past dense_len, where their kept sets
    differ, and at which margins (last kept against first dropped, in the
    reference's pass): SELECTION_TIE's reasons."""
    import numpy as np

    ref = family.reference
    k = sizes["sparse_config"]["topk"]
    mine = program_selection(cfg, params, tokens)
    theirs = [np.asarray(s) for *_, s in ref.streams(
        params, sizes, tokens, len(tokens), ref.TIE_PASS - 1, tell=True)
        if s is not None]
    out = {"positions": len(tokens), "selection_tie": ref.SELECTION_TIE,
           "by_layer": []}
    for a, b in zip(mine, theirs):
        both = (a >= 0) & (b >= 0)
        assert (both == (a >= 0)).all() and (both == (b >= 0)).all()
        full = both.sum(-1) > k         # selections with a block left over
        top = -np.sort(-b, axis=-1)
        margin = (top[..., k - 1] - top[..., k])[full]
        kept = lambda s: np.argsort(-s, -1, kind="stable")[..., :k]  # noqa: E731,E501
        differ = (np.sort(kept(a), -1) != np.sort(kept(b), -1)).any(-1)[full]
        d = np.abs(a - b)[both]
        out["by_layer"].append({
            "selections": int(full.sum()),
            "score_median": float(np.median(b[both])),
            "score_diff_max": float(d.max()),
            "score_diff_rms": float(np.sqrt(np.mean(d ** 2))),
            "margin_median": float(np.median(margin)),
            "margin_under_the_tie_share": float(
                np.mean(margin < ref.SELECTION_TIE)),
            "selections_that_differ_share": float(differ.mean()),
            "widest_margins_that_differ": sorted(
                float(m) for m in margin[differ])[-8:]})
    return out


def probe(family, params, sizes: dict, tokens: list, prompt_len: int) -> dict:
    """Over one sequence of random ids, its first `prompt_len` a prompt's,
    by the plain reference: the logits (top, top-2 margin, how often the
    best token is the one just read), and by layer what the mixer and the
    feed-forward add to the stream against the stream itself (rms)."""
    import jax.numpy as jnp
    import numpy as np

    ref = family.reference
    lg = np.asarray(ref.logits(params, sizes, tokens,
                               prompt_len=prompt_len))
    top2 = np.partition(lg, -2, axis=-1)[:, -2:]
    out = {"positions": len(tokens), "prompt_len": prompt_len,
           "logit_std": float(lg.std()),
           "top_logit_mean": float(top2[:, 1].mean()),
           "top2_margin_median": float(np.median(top2[:, 1] - top2[:, 0])),
           "best_is_the_token_just_read_share": float(np.mean(
               lg.argmax(-1) == np.asarray(tokens)))}
    added = []
    streams = ref.streams(params, sizes, tokens, prompt_len)
    rms = lambda a: float(jnp.sqrt(jnp.mean(a * a)))  # noqa: E731
    for (x0, x1, x2, _), what in zip(streams, sizes["mixer_types"]):
        added.append({"mixer": what, "stream_rms": rms(x0),
                      "mixer_adds_rms": rms(x1 - x0),
                      "ffn_adds_rms": rms(x2 - x1)})
    out["by_layer"] = added
    return out


def main(argv) -> int:
    import jax
    import numpy as np

    from benchmarks.harness import loader
    from benchmarks.harness.replica import seeded_params
    from benchmarks.tools import lfm2_moe_faults as harness_rule

    cell = loader.load_cell("minicpmsala-serve-longdocs-closed")
    family, sizes = cell.family, cell.family.sizes(cell.config)
    family.WEIGHTS.update(json.loads(os.environ.get("FAULTS_WEIGHTS", "{}")))
    cfg = family.program_config(sizes)
    seed = int(os.environ.get("FAULTS_SEED", 4900000101))
    params = seeded_params(family.model(cfg), seed)
    print(json.dumps({"device": str(jax.devices()[0]), "seed": seed,
                      "weights": family.WEIGHTS}), flush=True)
    rng = np.random.default_rng(seed)
    V = sizes["vocab_size"]
    if "probe" in argv or not argv:
        tokens = rng.integers(0, V, size=9216).tolist()
        print(json.dumps({"probe": probe(family, params, sizes, tokens,
                                         9088)}), flush=True)
    if "selection" in argv or not argv:
        tokens = rng.integers(0, V, size=9216).tolist()
        print(json.dumps({"selection": selection_probe(
            family, cfg, params, sizes, tokens)}), flush=True)
    # Two slots and four prompts (both slots reused): one under dense_len
    # to its end, one that crosses it while it decodes, two past it; 160
    # tokens each.  LONG: one prompt past dense_len and 2,304 tokens, for
    # what shows only once a block written while decoding has left the
    # window (2,048 tokens later) and competes through its compressed keys.
    dense_len = sizes["sparse_config"]["dense_len"]
    engine = dict(max_batch=2, max_len=12800, page_size=64, decode_chunk=8)
    short = ([rng.integers(0, V, size=n).tolist() for n in
              (3000, dense_len - 70, dense_len + 900, 12000)], 160)
    long = (short[0][2:3], 2304)
    names = [a for a in argv if a not in ("probe", "selection")] \
        or ([] if argv else ["sound", *FAULTS, "sound_long"])
    for name in names:
        prompts, new = long if name in LONG else short
        samples, counted = serve(cfg, params, engine, prompts, new,
                                 None if name.startswith("sound") else name)
        print(json.dumps({"fault": name, "new_tokens": new,
                          **harness_rule.judged(
                              family, params, sizes, engine["max_len"],
                              samples),
                          "counted": counted}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
