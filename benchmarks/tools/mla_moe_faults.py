"""Planted faults of the `mla_moe` family, and what `correct` makes of
each: the ways ISSUE 45 names in which a program can get latent attention
or the routed layer with shared experts wrong, each a patch of
`ray_tpu/models/mla_moe.py` (or of the routed layer it shares with
`models/lfm2_moe.py`) that leaves everything else as it is.  The CPU
tests plant them at tiny sizes (`tests/test_mla_moe.py`); on the chip, at
the published widths,

    chiprun --timeout 3000 -- python3 benchmarks/tools/mla_moe_faults.py [fault ...]

serves the same prompts through a real `LLMEngine` under each fault and
judges the streams by the harness's own rule (`replica.check_reference`,
then `serve_common.judge`: `tools/lfm2_moe_faults.py`'s `serve` and
`judged`), one JSON line a fault, the sound engine's first (CONTROLS are
the tolerance's: the nearest precision below the configuration's, in the
experts' products, in the latent kernel's and in the cache); `probe` before
them reads what the configuration file states of the seeded weights (the
reference's top logit and margins, how often the selection bias changes
the chosen set, how far the program's router scores lie from the
reference's float32 and served passes, and at which margins the program
first departs from the served pass: ROUTING_TIE's reasons).
`FAULTS_PROBE_AGAINST` names the passes the probe holds the program to
(`float32,served`; the served pass is always the last and the one the
margins are read in).  `FAULTS_WEIGHTS` (a JSON object) replaces deviations of
`families/mla_moe.WEIGHTS` for the run, and `FAULTS_TIE` the tie (a number;
`auto`: TIE_ROOM times the widest of the probe's two readings, rounded up
to two digits): how the committed values were chosen.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _module():
    from ray_tpu.models import mla_moe

    return mla_moe


def _shared_module():
    from ray_tpu.models import lfm2_moe

    return lfm2_moe


def _route_with(**how):
    """A `route` that departs from the sound one as `how` says."""
    import jax
    import jax.numpy as jnp

    def route(logits, bias, top_k, eps=1e-20):
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        top, idx = jax.lax.top_k(s + bias, top_k)
        chosen = top if how.get("biased_gates") \
            else jnp.take_along_axis(s, idx, axis=-1)
        gates = chosen if how.get("no_renorm") \
            else chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)
        return idx, gates / how.get("scaled_down", 1.0)

    return route


def _no_latent_norm(sound):
    class NoLatentNorm(sound):
        """Every norm but the latent's, which alone is narrower than the
        stream (the scale is still declared)."""

        def __call__(self, x):
            out = sound.__call__(self, x)
            return x if out.shape[-1] != self.parent.cfg.d_model else out

    return NoLatentNorm


def _rotate_halves(sound):
    import jax.numpy as jnp

    def rotate(x, positions, theta):
        d = x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = positions[..., None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    return rotate


def _scale_by_nope(sound):
    return lambda cfg: 1.0 / math.sqrt(cfg.d_nope)


def _prefill_scaled_by_nope(sound):
    # (the flash kernel scales by the root of its one width: the fault is
    # planted on the query)
    def attention(q, k, v, scale, impl, dtype):
        if impl == "flash":
            q = q * (scale * math.sqrt(q.shape[-1]))
        return sound(q, k, v, scale, impl, dtype)

    return attention


def _no_shared(sound):
    def call(self, u):
        out = sound(self, u)
        return 0.0 * out if self.label == "shared_expert" else out

    return call


# name -> [(what is patched, its attribute, sound -> faulty), ...]
FAULTS = {
    "i_latent_norm_left_out": [(_module, "RMSNorm", _no_latent_norm)],
    "ii_rotation_not_interleaved": [
        (_module, "rotate_interleaved", _rotate_halves)],
    "iii_scores_over_root_128": [
        (_module, "sm_scale", _scale_by_nope),
        (_module, "causal_mixed_width", _prefill_scaled_by_nope)],
    # (the routed layer is `models/lfm2_moe.py`'s own: patched there)
    "iv_scaling_factor_left_out": [
        (_shared_module, "route",
         lambda sound: _route_with(scaled_down=2.446))],
    "v_no_renormalisation": [
        (_shared_module, "route",
         lambda sound: _route_with(no_renorm=True))],
    "vi_selection_bias_as_a_gate": [
        (_shared_module, "route",
         lambda sound: _route_with(biased_gates=True))],
    "vii_shared_experts_left_out": [
        (lambda: _module().GatedMLP, "__call__", _no_shared)],
}
# Not faults of ISSUE 45's list of mistakes: controls of the tolerance,
# each the nearest precision below what the configuration states, with
# everything else as it is.  The first is the list's last entry: the
# grouped products take their rows in ONE bfloat16 term (a plain bf16
# program's experts).  The other two are on the latent path: the decode
# kernel's query and softmax weights in one bfloat16 term; and the cached
# rows kept to 3 bits after the leading one (what a float8 e4m3 cache
# keeps), in the prefill's decompression and in the pages alike.
def _one_term(sound):
    from benchmarks.tools.lfm2_moe_faults import _one_term as one_term

    return one_term(sound)


def _kernel_module():
    from ray_tpu.ops import paged_attention

    return paged_attention


def _rows_in_one_term(sound):
    import jax
    import jax.numpy as jnp

    def against_rows(a, rows, contract):
        if rows.dtype == jnp.float32:
            return sound(a, rows, contract)
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), rows, (((1,), (contract,)), ((), ())),
            preferred_element_type=jnp.float32)

    return against_rows


def _rows_to_8_bits(sound):
    import jax
    import jax.numpy as jnp

    def project(self, u, positions):
        q_nope, q_rope, rows = sound(self, u, positions)
        # round to nearest at the fourth mantissa bit of a bfloat16
        bits = jax.lax.bitcast_convert_type(rows.astype(jnp.bfloat16),
                                            jnp.uint16)
        bits = (bits + jnp.uint16(0x0008)) & jnp.uint16(0xFFF0)
        return q_nope, q_rope, jax.lax.bitcast_convert_type(
            bits, jnp.bfloat16).astype(rows.dtype)

    return project


CONTROLS = {
    "experts_in_one_bf16_term": [(_shared_module, "_two_terms", _one_term)],
    "kernel_query_and_weights_in_one_bf16_term": [
        (_kernel_module, "_against_rows", _rows_in_one_term)],
    "latents_kept_to_8_bits": [
        (lambda: _module().LatentAttention, "project", _rows_to_8_bits)],
}
# (the kernel's call is jitted at module level: a trace made before the
# patch would be found again)
FORGETS_TRACES = {"kernel_query_and_weights_in_one_bf16_term"}


@contextlib.contextmanager
def planted(name: str | None):
    """The program with fault `name` in it (None: the sound program)."""
    import jax

    with contextlib.ExitStack() as stack:
        if name in FORGETS_TRACES:
            stack.callback(jax.clear_caches)    # (after the patch is gone)
        for where, attr, make in ({**FAULTS, **CONTROLS}[name]
                                  if name else ()):
            target = where()
            stack.enter_context(mock.patch.object(
                target, attr, make(getattr(target, attr))))
        if name in FORGETS_TRACES:
            jax.clear_caches()
        yield


# ---------------------------------------------------------------------------
# What the seeded weights give (the configuration file's `assumed.weights`)
# ---------------------------------------------------------------------------


def program_scores(cfg, params, tokens, decode_from: int,
                   take: list | None = None) -> list:
    """The program's biased router scores, a routed layer: (S, E) over
    `tokens`, positions before `decode_from` by one prefill and the rest by
    decode steps through latent pages (one sequence, run eagerly, `route`
    made to tell what it was handed).  `take`: a routed layer's (S, k)
    experts that the program is made to take in place of its own choice
    (its gates from its own scores): what is then read is arithmetic
    alone, no selection that fell the other way upstream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_families import family_of

    mod = _shared_module()
    seen: list = []
    sound = mod.route
    at = {"rows": None}     # the sequence positions of the call's rows

    def telling(logits, bias, top_k, eps=1e-20):
        s = jax.nn.sigmoid(logits)
        layer = len(seen) % layers_in_all
        seen.append(np.asarray(s + bias))
        idx, gates = sound(logits, bias, top_k, eps)
        if take is None:
            return idx, gates
        rows = at["rows"]
        given = jnp.asarray(take[layer])[jnp.clip(rows, 0, len(tokens) - 1)]
        idx = jnp.where((rows < len(tokens))[:, None], given, idx)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return idx, chosen / (jnp.sum(chosen, -1, keepdims=True) + eps)

    layers_in_all = cfg.n_layers - cfg.n_dense_layers
    page = 64
    n_pages = -(-len(tokens) // page) + 1
    fam = family_of(cfg, n_pages * page)
    toks = jnp.asarray(tokens, jnp.int32)
    with mock.patch.object(mod, "route", telling):
        padded = jnp.zeros((1, -(-decode_from // page) * page),
                           jnp.int32).at[0, :decode_from].set(
                               toks[:decode_from])
        at["rows"] = jnp.where(jnp.arange(padded.shape[1]) < decode_from,
                               jnp.arange(padded.shape[1]), len(tokens))
        _, fresh, _ = fam.prefill(params, padded,
                                  jnp.asarray([decode_from - 1]))
        rows = [s[:decode_from] for s in seen]
        state = fam.init_state(1, n_pages + 1, page)
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        state = fam.write_prompt(state, fresh, jnp.asarray([0]),
                                 table[:, : padded.shape[1] // page])
        del seen[:]
        for t in range(decode_from, len(tokens)):
            pos = jnp.asarray([t], jnp.int32)
            at["rows"] = pos
            _, state, _ = fam.decode(params, toks[t: t + 1], pos, state,
                                     table, pos, None)
    for i in range(layers_in_all):
        rows[i] = np.concatenate([rows[i]] + seen[i::layers_in_all], axis=0)
    return rows


def probe(family, cfg, params, sizes: dict, tokens: list,
          decode_from: int) -> dict:
    """Over one sequence of random ids, its first `decode_from` positions a
    prompt's: the reference's logits (top, top-2 margin), the served pass's
    router margins and how often the bias changes the chosen set; how far
    the program's router scores lie from the reference's float32 and served
    passes when it is made to take that pass's experts
    (arithmetic alone); and where the program, left to itself, first
    departs from the served pass at a position, that selection's margin in
    the served pass (what ROUTING_TIE has to cover)."""
    import numpy as np

    ref = family.reference
    k = sizes["num_experts_per_tok"]
    out = {"positions": len(tokens), "decode_from": decode_from,
           "routing_tie": ref.ROUTING_TIE}
    first = sizes["first_k_dense_replace"]
    bias = [np.asarray(params["params"][f"layers_{i}"]["experts"][
        "expert_bias"]) for i in range(first, sizes["num_hidden_layers"])]
    chosen = lambda s: np.sort(np.argsort(-s, -1)[:, :k], -1)  # noqa: E731
    rms = lambda d: float(np.sqrt(np.mean(d ** 2))) if d.size else 0.0  # noqa: E731,E501
    judged_from = min(512, decode_from // 2)
    out["judged_from"] = judged_from

    def scores_of(level):
        scores: list = []
        ref.hidden_states(params, sizes, tokens, level, scores,
                          prompt=decode_from)
        return [np.asarray(s) for s in scores]

    # arithmetic alone: the program made to take a pass's experts;
    # positions from `judged_from` on are as far into a context as the
    # cell's judged ones (an early position's attention is most of it)
    served = None
    against = os.environ.get("FAULTS_PROBE_AGAINST", "float32,served")
    for name, level in (("float32", 0), ("served", ref.SERVED)):
        if name not in against.split(","):
            continue
        served = scores_of(level)
        forced = program_scores(cfg, params, tokens, decode_from, take=[
            np.argsort(-s, -1)[:, :k] for s in served])
        by_layer = []
        for a, b in zip(forced, served):
            d = np.abs(a - b)
            by_layer.append({
                "prefill_max": float(d[judged_from:decode_from].max()),
                "prefill_rms": rms(d[judged_from:decode_from]),
                "early_prefill_max": float(d[:judged_from].max()),
                "decode_max": float(d[decode_from:].max()),
                "decode_rms": rms(d[decode_from:])})
        out[f"router_score_diff_with_the_{name}_passs_experts"] = by_layer
    margins, changed = [], []
    for s, b in zip(served, bias):
        top = -np.sort(-s, axis=-1)
        margins.append(top[:, k - 1] - top[:, k])
        changed.append(float(np.mean(
            (chosen(s) != chosen(s - b)).any(-1))))
    flat = np.concatenate(margins)
    out["router_margin_median"] = float(np.median(flat))
    out["router_margin_p01"] = float(np.quantile(flat, 0.01))
    out["router_margin_under_tie_share"] = float(
        np.mean(flat < ref.ROUTING_TIE))
    out["bias_changes_the_set_share"] = float(np.mean(changed))
    out["experts_chosen_by_64_rows"] = float(np.mean([
        len(np.unique(chosen(s[i: i + 64])))
        for s in served for i in range(0, len(s) - 64, 64)]))
    # the program left to itself, against the served pass
    mine = program_scores(cfg, params, tokens, decode_from)
    differs = np.stack([(chosen(a) != chosen(b)).any(-1)
                        for a, b in zip(mine, served)])     # (layers, S)
    out["selections_that_differ_share_by_layer"] = [
        float(np.mean(d)) for d in differs]
    where = np.flatnonzero(differs.any(0))
    at = differs[:, where].argmax(0)            # a position's first layer
    first_margin = np.stack(margins)[at, where]
    judged = where >= judged_from
    out["positions_that_depart_from_the_served_pass"] = int(len(where))
    out["first_departures_margins_judged"] = sorted(
        float(m) for m in first_margin[judged])[-12:]
    out["first_departures_margins_early_prefill"] = sorted(
        float(m) for m in first_margin[~judged])[-4:]
    lg = np.asarray(ref.logits(params, sizes, tokens))
    top2 = np.partition(lg, -2, axis=-1)[:, -2:]
    out["logit_std"] = float(lg.std())
    out["top_logit_mean"] = float(top2[:, 1].mean())
    out["top2_margin_median"] = float(np.median(top2[:, 1] - top2[:, 0]))
    return out


# The tie over the widest reading: a selection falls the other way when
# the DIFFERENCE of two scores' errors passes its margin, and 1,024
# positions are a small sample of a run's 40,000.
TIE_ROOM = 1.5


def tie_from(probed: dict) -> float:
    """TIE_ROOM times the widest of: a score's difference from the served
    pass's at a judged position (the program taking that pass's experts),
    and the margin at which the program first departed from it; rounded
    up to two digits."""
    widest = max(
        [max(layer["prefill_max"], layer["decode_max"]) for layer in
         probed["router_score_diff_with_the_served_passs_experts"]]
        + probed["first_departures_margins_judged"])
    digits = 10.0 ** (math.floor(math.log10(widest * TIE_ROOM)) - 1)
    return math.ceil(widest * TIE_ROOM / digits) * digits


def main(argv) -> int:
    import jax
    import numpy as np

    from benchmarks.harness import loader
    from benchmarks.harness.replica import seeded_params
    from benchmarks.tools import lfm2_moe_faults as harness_rule

    cell = loader.load_cell("kimivl-serve-pages-closed")
    family, sizes = cell.family, cell.family.sizes(cell.config)
    family.WEIGHTS.update(json.loads(os.environ.get("FAULTS_WEIGHTS", "{}")))
    cfg = family.program_config(sizes)
    seed = int(os.environ.get("FAULTS_SEED", 4500000101))
    params = seeded_params(family.model(cfg), seed)
    print(json.dumps({"device": str(jax.devices()[0]), "seed": seed,
                      "weights": family.WEIGHTS}), flush=True)
    rng = np.random.default_rng(seed)
    V = sizes["vocab_size"]
    tie = os.environ.get("FAULTS_TIE")
    if "probe" in argv or not argv:
        tokens = rng.integers(0, V, size=1024).tolist()
        probed = probe(family, cfg, params, sizes, tokens, 896)
        print(json.dumps({"probe": probed}), flush=True)
        if tie == "auto":
            tie = tie_from(probed)
    if tie:
        family.reference.ROUTING_TIE = float(tie)
        family.reference.layer.clear_cache()    # (read where it is traced)
    print(json.dumps({"routing_tie": family.reference.ROUTING_TIE}),
          flush=True)
    # Four slots and nine prompts (every slot reused), rows of unequal
    # length in one padded bucket of each of two sizes, as
    # `tests/test_mla_moe.py` has them at tiny sizes.
    engine = dict(max_batch=4, max_len=1280, page_size=64, decode_chunk=8)
    prompts = [rng.integers(0, V, size=n).tolist()
               for n in (300, 1000, 520, 640, 270, 900, 430, 777, 512)]
    names = [a for a in argv if a != "probe"] \
        or ["sound", *FAULTS, *CONTROLS]
    for name in names:
        with planted(None if name == "sound" else name):
            samples, counted = harness_rule.serve(cfg, params, engine,
                                                  prompts, 160)
        print(json.dumps({"fault": name, **harness_rule.judged(
            family, params, sizes, engine["max_len"], samples),
            "counted": counted}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
