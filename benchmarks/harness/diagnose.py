"""What to look at when a served stream and the plain reference part ways:
the discriminating runs of a `benchmark` issue, kept as code.  Run only
where `BENCH_DIAGNOSE=1` is set (`serve_common.DIAGNOSE_ENV`); the result
goes into the sample's entry of the run's `reference` log line and plays
no part in `correct`.

Three questions, in this order (PERF.md section 6, PR 27, has the answers
that were read with it):

1. Alone against under load.  The sample's prompt is sent again, alone, to
   the now empty engine.  Every program of the engine computes a greedy
   stream's rows independently of its neighbours, so other tokens than
   under load point at the engine (pages, tables, lengths) - unless the
   two streams part at a near-tie, which the reference's margin there
   shows.
2. Cache path against whole forward, in the served type.  The system's own
   model, teacher-forced over prompt + generated tokens without any cache:
   where it sides with the reference against the engine, the fault is on
   the prefill -> pages -> paged-decode path; where it sides with the
   engine, the context cannot be decided in the served type.
3. The reference's own stability: what its rounded passes do at the
   positions the engine misses (`replica.check_reference` makes them).
"""

from __future__ import annotations


def sample(replica, smp: dict, lg, over, moved, passes,
           tolerance: float) -> dict:
    import numpy as np

    prompt, got = smp["prompt"], np.asarray(smp["output"])
    eng = replica.engine
    out = {"k_mod_chunk": [int(k % eng.decode_chunk) for k in over],
           "pos_mod_page": [int((len(prompt) + k) % eng.page_size)
                            for k in over],
           # Each rounded pass at the engine's misses: how far the
           # reference's own token moved there, and whether the pass's
           # best is the engine's token.
           "moved_at_over": [[float(m[k]) for k in over] for m in moved],
           "pass_picks_engine_token": [
               [bool(p[k].argmax() == got[k]) for k in over]
               for p in passes],
           # The whole population, for the cap: positions each pass
           # alone would set aside.
           "moved_max": [float(m.max()) for m in moved],
           # The control (K and V in 8 bits, teacher-forced on the same
           # tokens): the widest gap of the token it puts first.
           "control_8bit_gap": _control_gap(replica, smp, lg)}
    if not len(over):
        return out
    out["alone"] = _alone(replica, smp, tolerance)
    out["residual_peak"] = _residual_peak(replica, smp, over)
    out["divergence_by_layer"] = _divergence_by_layer(replica, smp, over)
    out["whole_forward"] = _whole_forward(replica, prompt, got, lg, over)
    return out


def _control_gap(replica, smp: dict, lg) -> float:
    import numpy as np

    ctl = replica.reference_logits(smp["prompt"], smp["output"], kv_bits=8)
    return float((lg.max(-1) - lg[np.arange(len(lg)), ctl.argmax(-1)]).max())


def _residual_peak(replica, smp: dict, over) -> dict | None:
    """The reference's last residual stream: its largest coordinate over
    its root mean square, at the engine's misses and over all generated
    positions.  One coordinate far above the rest leaves every projection
    of the normed stream a large common part plus the small part that
    tells positions apart, and bfloat16 outputs keep eight bits of the
    sum."""
    import numpy as np

    hidden = getattr(replica._family.reference, "hidden_states", None)
    if hidden is None:
        return None
    seq = list(smp["prompt"]) + list(smp["output"][:-1])
    x = np.asarray(hidden(replica._params, replica._sizes, seq))[
        len(smp["prompt"]) - 1:]
    peak = np.abs(x).max(-1) / np.sqrt((x * x).mean(-1))
    return {"at_over": [float(peak[k]) for k in over],
            "median": float(np.median(peak)), "max": float(peak.max()),
            "argmax": int(peak.argmax())}


def _divergence_by_layer(replica, smp: dict, over) -> dict | None:
    """After each layer, how far the reference in the whole served type
    (its last rounded pass) has moved from the float32 reference, as a
    share of the float32 residual's norm: at the engine's misses, and the
    median over the generated positions.  A jump at one layer names the
    layer that cannot be decided; a steady climb says the context as a
    whole is the cause.  (For references that have a `layer`.)"""
    import jax.numpy as jnp
    import numpy as np

    ref, sizes = replica._family.reference, replica._sizes
    if not hasattr(ref, "layer"):
        return None
    seq = list(smp["prompt"]) + list(smp["output"][:-1])
    first = len(smp["prompt"]) - 1
    p = replica._params["params"]
    x0 = x3 = p["embed"]["embedding"][jnp.asarray(seq)].astype(jnp.float32)
    how = dict(n_heads=sizes["num_attention_heads"],
               n_kv_heads=sizes["num_key_value_heads"],
               theta=float(sizes["rope_theta"]),
               eps=float(sizes["rms_norm_eps"]))
    at_over, median = [], []
    for i in range(sizes["num_hidden_layers"]):
        x0 = ref.layer(x0, p[f"layers_{i}"], **how)
        x3 = ref.layer(x3, p[f"layers_{i}"], rounded=len(ref.ROUNDINGS) - 1,
                       **how)
        share = np.asarray(jnp.linalg.norm(x3 - x0, axis=-1)
                           / jnp.linalg.norm(x0, axis=-1))[first:]
        at_over.append([round(float(share[k]), 4) for k in over])
        median.append(round(float(np.median(share)), 4))
    return {"at_over": at_over, "median": median}


def _alone(replica, smp: dict, tolerance: float) -> dict:
    """The same prompt alone on the empty engine, and that stream against
    the reference."""
    import numpy as np

    from ray_tpu.models.generate import SamplingParams

    got = list(smp["output"])
    again = replica.engine.submit(
        smp["prompt"], SamplingParams(max_new_tokens=len(got))).tokens()
    differ = [k for k, (a, b) in enumerate(zip(got, again)) if a != b]
    lg = replica.reference_logits(smp["prompt"], again)
    gap = lg.max(-1) - lg[np.arange(len(again)), np.asarray(again)]
    return {"same_tokens": not differ,
            "first_difference": differ[0] if differ else None,
            "over": [[int(k), float(gap[k])]
                     for k in np.flatnonzero(gap > tolerance)]}


def _whole_forward(replica, prompt, got, lg, over) -> dict:
    """The system's model in its served type, no cache, teacher-forced."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + [int(t) for t in got[:-1]]
    n = -(-len(seq) // 256) * 256          # few shapes, few compiles
    tokens = jnp.asarray([seq + [0] * (n - len(seq))], jnp.int32)
    rows = jnp.arange(len(prompt) - 1, len(seq))
    model = replica._family.model(replica._cfg)
    wf = np.asarray(jax.jit(
        lambda p, t: model.apply(p, t)[0][rows])(replica._params, tokens))
    at = np.arange(len(got))
    pick = wf.argmax(-1)
    return {
        # At the engine's misses: its token under the whole forward's
        # best (0: the whole forward decodes as the engine did), and the
        # whole forward's token under the reference's best (0: it sides
        # with the reference).
        "engine_under_whole": [float(wf[k].max() - wf[k, got[k]])
                               for k in over],
        "whole_under_reference": [float(lg[k].max() - lg[k, pick[k]])
                                  for k in over],
        "whole_picks_engine_token": int((pick == got).sum()),
        "whole_under_reference_max": float(
            (lg.max(-1) - lg[at, pick]).max())}


def events_near(span: dict, replica_spans: list, ks: list,
                before_s: float = 0.5) -> list:
    """For generated index k of the client's `span`: which other requests
    got their first token (admitted and prefilled) or their last (slot
    and pages freed) in the `before_s` before the client saw token k.
    Both clocks are this host's `time.monotonic()`."""
    out = []
    for k in ks:
        t = span["token_times"][k]
        near = []
        for s in replica_spans:
            if s["rid"] == span["rid"]:
                continue
            for what in ("first", "last"):
                if s[what] is not None and t - before_s <= s[what] <= t:
                    near.append([what, s["rid"], s["prompt_len"],
                                 round(t - s[what], 3)])
        out.append({"k": int(k), "events": near})
    return out
