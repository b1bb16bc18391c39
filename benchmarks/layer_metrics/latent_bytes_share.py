"""engine: the share of a decode step's bytes that are cached latents.
100 x latent bytes read / (latent bytes + weight bytes touched), summed
over the `engine.decode.wait` spans of the window.  A chunk's span carries
`latent_tokens` (the tokens resident under its occupied rows, summed over
its steps: what ONE layer's kernel reads) and `experts_touched` (summed
over steps and routed layers); a token's bytes over all layers, unpadded
(8,064), are `mla_moe_costs.latent_bytes_per_token`, the weights a step
reads whatever the routing `mla_moe_costs.other_bytes`.  Which regime the cell is in: a sixth at
190,000 resident tokens, more as contexts grow.  None on a program that
counts no latents."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "batch_tokens_per_s"

costs = sibling_reader(__file__, "mla_moe_costs")
program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(obs.get("window"))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    chunks = [a for a in chunks if "latent_tokens" in a]
    if not chunks or obs.get("family") != "mla_moe":
        return None
    sizes = obs["sizes"]
    steps = obs["config"]["serve"]["engine"]["decode_chunk"]
    latent = sum(a["latent_tokens"] for a in chunks) \
        * costs.latent_bytes_per_token(sizes)
    weights = len(chunks) * steps * costs.other_bytes(sizes) \
        + sum(a["experts_touched"] for a in chunks) \
        * costs.expert_bytes(sizes)
    return 100.0 * latent / (latent + weights)
