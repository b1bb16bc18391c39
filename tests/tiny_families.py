"""The seven served families at tiny widths, in ONE place: for each its tiny
configuration (the model file's own, `benchmarks/families` checks it), the
sizes its plain reference reads, its seeded weights (made once a process, on
first use), and "is this stream what the plain reference decodes greedily".

A plain module: no test in it, and importing it starts no backend (every
jax import is inside a function; weights are made under `jax_platforms`
cpu, as the fixtures that used to make them set it).  A test module takes
its family from here and imports no other test module; a `model_config` PR
adds its family HERE, not a copy of another family's test file.

    from tests.tiny_families import granite_hybrid as tiny
    eng = LLMEngine(tiny.cfg, tiny.params, **ENGINE)
    assert tiny.is_greedy(prompt, eng.generate(prompt, ...))
"""

import functools
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# The engine the served tests of every family build, and the one of the
# tests that watch the loop itself (`test_llm_resident_args.py`,
# `test_llm_overlap.py`: three slots, chunks of LOOP_K).
ENGINE = dict(max_batch=4, max_len=128, page_size=16, decode_chunk=4)
LOOP_K = 4
LOOP_ENGINE = dict(max_batch=3, max_len=128, page_size=16,
                   decode_chunk=LOOP_K)


def _cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def prompts(seed, lengths, vocab=256) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lengths]


def serve(eng, prompts, new) -> list:
    """Every prompt submitted to a held engine, then let go: the batched
    prefills and the admissions mid-flight are the engine's to order."""
    from ray_tpu.models.generate import SamplingParams

    eng.quiesce_for_drain()
    handles = [eng.submit(p, SamplingParams(max_new_tokens=new))
               for p in prompts]
    eng.resume()
    return [h.tokens() for h in handles]


class Family:
    """A family's tiny model, and its plain reference as the judge."""

    name = ""
    SIZES: dict = {}            # what `benchmarks/reference/<name>` reads

    @functools.cached_property
    def cfg(self):
        raise NotImplementedError

    def make(self, cfg, seed=0):
        """The family's seeded weights at `cfg`'s widths and type."""
        raise NotImplementedError

    @functools.cached_property
    def params(self):
        _cpu()
        return self.make(self.cfg)

    def reference(self, params, seq, rows=None, sizes=None, **how):
        """The plain reference's logits of one sequence, at `rows`."""
        import importlib

        ref = importlib.import_module(f"benchmarks.reference.{self.name}")
        return np.asarray(ref.logits(params, sizes or self.SIZES, list(seq),
                                     rows, **how))

    def reference_gap(self, prompt, output, params=None):
        """How far the reference's logit of each engine token lies under
        the reference's best, teacher-forced over prompt + output (the
        rule of the benchmark's `correct`), and the share of positions at
        which the reference's best is the token just read."""
        seq = list(prompt) + list(output[:-1])
        rows = list(range(len(prompt) - 1, len(seq)))
        lg = self.reference(self.params if params is None else params, seq,
                            rows)
        repeats = (lg.argmax(-1) == np.asarray(seq)[rows]).mean()
        return lg.max(-1) - lg[np.arange(len(output)), output], repeats

    def is_greedy(self, prompt, output) -> bool:
        return self.reference_gap(prompt, output)[0].max() == 0.0

    @functools.cached_property
    def rewinds(self) -> bool:
        """Whether the engine may run a decode step of this family again."""
        from ray_tpu.serve.llm_families import family_of

        return family_of(self.cfg, ENGINE["max_len"]).rewinds


class Dense(Family):
    """The Llama decoder the dense serve tests share (d 64, 2 layers, 4
    query and 2 KV heads, float32); its reference is the one-shot
    `Generator`, the spec for greedy decoding: bit-equal tokens."""

    name = "dense"

    @functools.cached_property
    def cfg(self):
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaConfig

        return LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                           n_kv_heads=2, d_ff=128, max_seq_len=128,
                           dtype=jnp.float32, attention="reference",
                           remat=False)

    def make(self, cfg, seed=0):
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaModel

        return LlamaModel(cfg).init(_cpu().random.PRNGKey(seed),
                                    jnp.zeros((1, 8), jnp.int32))

    @functools.lru_cache(maxsize=None)
    def _greedy(self, prompt: tuple, n_new: int) -> list:
        from ray_tpu.models.generate import Generator, SamplingParams

        gen = Generator(self.cfg, self.params, batch=1,
                        max_len=len(prompt) + n_new)
        return gen.generate(np.asarray([prompt], np.int32),
                            SamplingParams(max_new_tokens=n_new))[0].tolist()

    def greedy(self, prompt, n_new) -> list:
        """The Generator's greedy tokens (a prompt's are made once)."""
        return list(self._greedy(tuple(int(t) for t in prompt), n_new))

    def is_greedy(self, prompt, output) -> bool:
        return list(output) == self.greedy(prompt, len(output))


def _scaled(weights, factors):
    return dict(weights, **{k: weights[k] * f for k, f in factors.items()})


class Recurrent(Family):
    """A family with fixed per-slot state beside its pages, at the model's
    own level: a padded prefill, its cache cut into the pages of a pool,
    then teacher-forced paged decode steps against the reference."""

    PAGE, TABLE, BUCKET = 4, 16, 32
    # What `tests/test_families_models.py` holds the family to.  Both sides
    # compute in float32 in different orders: TOL on logits leaves room
    # above what that moves and is far under what K and V held in bfloat16
    # cost; STATE_TOL on a row's fixed state; SERVED_TOL on the bfloat16
    # engine path over the decode below.
    TOL = STATE_TOL = SERVED_TOL = 0.0
    # (seed of the two sequences of 60, prompt lengths, decode steps): 24
    # steps go three times round SambaY's window of 8, through pages of 4.
    DECODE = (0, [21, 13], 24)
    ROWS = ((3, 27), (4, 11))   # (seed, length) of a padded bucket's rows
    FIXED = ()                  # what of a prefill's state is a row's own

    def model(self, cfg=None):
        raise NotImplementedError

    def check_reference(self, want):
        """What the reference's logits of a sequence must look like for
        the comparison to mean something."""

    def check_row(self, padded_logits, alone_logits):
        """A row of a padded bucket beside the same row prefilled alone."""

    def check_prefill(self, both):
        """The shape of a prefill's state over `ROWS`."""
        raise NotImplementedError

    def paged_state(self, fresh, batch):
        """The prefill's state with its K and V cut into the pages of
        pools -> state, table: row b owns pages 1 + b * TABLE ..., page 0
        is nobody's."""
        raise NotImplementedError

    def _decode(self, model, params, token, state, table, length):
        raise NotImplementedError

    def tokens(self, seed, shape):
        return np.random.default_rng(seed).integers(1, 256, size=shape)

    @functools.lru_cache(maxsize=None)
    def _programs(self, model):
        """A model's prefill and decode step, jitted once for every test
        of the process that runs them."""
        jax = _cpu()
        prefill = jax.jit(lambda p, tokens, last: model.apply(
            p, tokens, last, method=type(model).prefill))
        decode = jax.jit(lambda p, t, s, table, ln: self._decode(
            model, p, t, s, table, ln))
        return prefill, decode

    def prefill(self, model, params, rows, bucket, last=None):
        """Right-padded rows through `prefill` -> logits, state."""
        padded = np.zeros((len(rows), bucket), np.int32)
        for r, row in enumerate(rows):
            padded[r, : len(row)] = row
        if last is None:
            last = [len(row) - 1 for row in rows]
        return self._prefill(model, params, padded,
                             np.asarray(last, np.int32))

    def _prefill(self, model, params, padded, last):
        import jax.numpy as jnp

        return self._programs(model)[0](params, jnp.asarray(padded),
                                        jnp.asarray(last))

    def _table(self, batch):
        import jax.numpy as jnp

        return jnp.asarray(1 + np.arange(batch * self.TABLE).reshape(
            batch, self.TABLE), jnp.int32)

    def _pool(self, a, table, batch):
        import jax.numpy as jnp

        B, H, S, D = a.shape
        PAGE = self.PAGE
        pages = a.reshape(B, H, S // PAGE, PAGE, D).transpose(0, 2, 1, 3, 4)
        out = jnp.zeros((1 + batch * self.TABLE, H, PAGE, D), a.dtype)
        return out.at[table[:, : S // PAGE].reshape(-1)].set(
            pages.reshape(-1, H, PAGE, D))

    def decode_against_reference(self, model, params, seqs, prompt_lens,
                                 steps, rounded=0, fault=None, **sizes):
        """Prefill the prompts in one bucket, then `steps` teacher-forced
        paged decode steps; the widest gap to the reference's full pass.
        `fault(what, state)` may spoil the state on its way."""
        import jax.numpy as jnp

        fault = fault or (lambda what, state: state)
        B = len(seqs)
        logits, fresh = self.prefill(
            model, params, [s[:n] for s, n in zip(seqs, prompt_lens)],
            self.BUCKET)
        state, table = self.paged_state(fault("prefilled", fresh), B)
        want = [self.reference(params, s, sizes=dict(self.SIZES, **sizes),
                               rounded=rounded) for s in seqs]
        gaps = [np.abs(np.asarray(logits[b]) - want[b][n - 1]).max()
                for b, n in enumerate(prompt_lens)]
        decode = self._programs(model)[1]
        length = jnp.asarray(prompt_lens, jnp.int32)
        for k in range(steps):
            token = jnp.asarray([s[n + k] for s, n in
                                 zip(seqs, prompt_lens)])
            logits, state = decode(params, token, state, table, length)
            state = fault("stepped", state)
            gaps += [np.abs(np.asarray(logits[b]) - want[b][n + k]).max()
                     for b, n in enumerate(prompt_lens)]
            length = length + 1
        return self.widest(gaps, model)

    def widest(self, gaps, model):
        """What `decode_against_reference` makes of its positions' gaps."""
        return max(gaps)


class SambaY(Recurrent):
    """Phi-4-mini-flash-reasoning's architecture: d 64, 8 layers in the
    published pattern (0-3 Mamba/window, 4 Mamba, 5 full, 6-7 GMU/cross),
    window 8, heads of 16, float32."""

    name = "sambay"
    SIZES = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=8,
                 num_attention_heads=4, num_key_value_heads=2,
                 sliding_window=8, vocab_size=256, layer_norm_eps=1e-5,
                 tie_word_embeddings=True, mamba_d_state=4, mamba_d_conv=4,
                 mamba_expand=2, mamba_dt_rank=4)
    # float32 reordering moves the logits (within +-1.2) by 1e-7 to 5e-7;
    # served: the plain bfloat16 whole forward is within 0.05 on the same
    # tokens, two-term products keep it within 0.02
    TOL, STATE_TOL, SERVED_TOL = 2e-5, 1e-6, 0.02
    DECODE = (5, [21, 13], 24)
    FIXED = ("mamba", "rings")

    def check_prefill(self, both):
        assert len(both["rings"]) == 2 and len(both["mamba"]) == 3

    @functools.cached_property
    def cfg(self):
        from ray_tpu.models.sambay import TINY_SAMBAY

        return TINY_SAMBAY

    def make(self, cfg, seed=0):
        from ray_tpu.models.sambay import init_params

        return init_params(cfg, _cpu().random.PRNGKey(seed))

    def model(self, cfg=None):
        from ray_tpu.models.sambay import SambaYModel

        return SambaYModel(cfg or self.cfg)

    @functools.lru_cache(maxsize=None)
    def serving(self, cfg, windows=None):
        """The family's class at a configuration: its prompt's programs,
        jitted once for every test of the process that runs them.
        `windows`: of a block, where not the family's own (the programs are
        traced at their first call: the block is set before)."""
        from ray_tpu.serve.llm_families import SambaYServing

        serving = SambaYServing(cfg, ENGINE["max_len"])
        if windows is not None:
            serving.block = windows * cfg.window
        return serving

    def _prefill(self, model, params, padded, last):
        """As the engine prefills them: a block of positions a program from
        the host, then the tail (`SambaYServing.prefill_from_host`)."""
        return self.serving(model.cfg).prefill_from_host(params, padded,
                                                         last)

    def paged_state(self, fresh, batch):
        table = self._table(batch)
        k, v = fresh["cache"]
        return {"mamba": fresh["mamba"], "rings": fresh["rings"],
                "pool": (self._pool(k, table, batch),
                         self._pool(v, table, batch))}, table

    def _decode(self, model, params, token, state, table, length):
        return model.apply(params, token, state, table, length,
                           method=type(model).decode)


class GraniteHybrid(Recurrent):
    """Granite-4.0-H's decoder: d 64, 8 layers (mamba, mamba, attention,
    mamba) x 2, Mamba-2 with 4 heads of 32 and state 16 in chunks of 8,
    attention with 4 query and 2 KV heads of 16, float32."""

    name = "granite_hybrid"
    SIZES = dict(
        hidden_size=64, intermediate_size=128, shared_intermediate_size=128,
        num_hidden_layers=8,
        layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
        mamba_n_groups=1, mamba_expand=2, mamba_chunk_size=8,
        attention_multiplier=0.015625, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8, rms_norm_eps=1e-5,
        position_embedding_type="nope", rope_theta=10000,
        max_position_embeddings=256, tie_word_embeddings=True,
        torch_dtype="float32")
    # float32 reordering moves the logits (within +-3.6) by up to 5e-6;
    # served: logits of deviation 0.91, measured 0.019
    TOL, STATE_TOL, SERVED_TOL = 3e-5, 1e-5, 0.06
    DECODE = (7, [21, 13], 24)
    ROWS = Recurrent.ROWS + ((5, 2),)
    FIXED = ("ssm",)

    def check_reference(self, want):
        assert 0.3 < want.std() < 1.0       # the published widths' scale

    def check_row(self, padded_logits, alone_logits):
        np.testing.assert_allclose(padded_logits, alone_logits,
                                   atol=self.TOL)

    def check_prefill(self, both):
        assert len(both["ssm"]) == 6 and len(both["kv"]) == 2
        # K and V of two KV heads of 16 lie side by side in one head of 32
        assert both["kv"][0][0].shape == (len(self.ROWS), 1, self.BUCKET, 32)

    @functools.cached_property
    def cfg(self):
        from ray_tpu.models.granite_hybrid import TINY_GRANITE

        return TINY_GRANITE

    def make(self, cfg, seed=0):
        """The benchmark's initialiser with the matrices' deviations scaled
        from the published width to this one (sqrt(2048 / 64)), so that
        activations, step sizes and attention scores have the scale they
        have at the published widths: the state then carries as much of a
        layer's output as the skip term does, and a fault in it shows.  The
        embedding keeps its deviation and the final norm's scale takes the
        factor instead: the logits' deviation is the published widths'
        (0.91), and the token just read, whose embedding enters the stream
        times 12 and is also its row of the head, is not what the layers
        are drowned by (with the embedding scaled too, greedy decoding here
        repeats its input at 19 positions in 20, whatever the state
        holds)."""
        from benchmarks.families.granite_hybrid import WEIGHTS
        from ray_tpu.models.granite_hybrid import init_params

        wider = (2048 / cfg.d_model) ** 0.5
        return init_params(
            cfg, _cpu().random.PRNGKey(seed), **_scaled(WEIGHTS, dict.fromkeys(
                ("in_std", "qkv_std", "out_std", "final_norm"), wider)))

    def model(self, cfg=None):
        from ray_tpu.models.granite_hybrid import GraniteHybridModel

        return GraniteHybridModel(cfg or self.cfg)

    def paged_state(self, fresh, batch):
        table = self._table(batch)
        return {"ssm": fresh["ssm"],
                "pools": [(self._pool(k, table, batch),
                           self._pool(v, table, batch))
                          for k, v in fresh["kv"]]}, table

    def _decode(self, model, params, token, state, table, length):
        return model.apply(params, token, length, state, table, length,
                           method=type(model).decode)


class GraniteMoeHybrid(GraniteHybrid):
    """Granite-4.0-H's decoder with its routed experts: the tiny dense
    member's mixers on a stream of 512, a shared feed-forward of 64 beside
    8 routed experts of 32, three a token (the gates a softmax over the
    three chosen logits), and 4 query and 2 KV heads of 128 as they are
    (the kernels' width: nothing paired); float32.  Every expert is held
    unless a test hands `share` its own."""

    name = "granite_moe_hybrid"
    SIZES = dict(
        GraniteHybrid.SIZES, hidden_size=512, intermediate_size=32,
        shared_intermediate_size=64, num_local_experts=8,
        num_experts_per_tok=3, logits_scaling=16, router_experts=8,
        experts_held=None)
    # float32 reordering moves the logits (within +-3.6) by up to 4.4e-5
    # and a row's state by 1.3e-5 (sums over a stream of 512, and the
    # grouped products sum a row's experts in another order); served
    # (`decode_against_reference`: at the WIDEST position, under the sets
    # the program took): measured 0.060 (0.095 against the reference's own
    # sets, 0.025 at the median position); the program took another set
    # than the reference at 4 of 656 selections, its expert 0.010-0.013
    # under the reference's third logit, where the median margin is 0.4
    TOL, STATE_TOL, SERVED_TOL = 1e-4, 3e-5, 0.1
    SERVED_TIE = 0.03

    def check_prefill(self, both):
        assert len(both["ssm"]) == 6 and len(both["kv"]) == 2
        # K and V of the two KV heads of 128 lie as they are
        assert both["kv"][0][0].shape == (len(self.ROWS), 2, self.BUCKET, 128)

    def prefill(self, model, params, rows, bucket, last=None):
        # (the routed member's prefill hands its counts back too)
        return super().prefill(model, params, rows, bucket, last)[:2]

    @functools.cached_property
    def cfg(self):
        from ray_tpu.models.granite_hybrid import TINY_GRANITE_MOE

        return TINY_GRANITE_MOE

    def share(self, first, count, cfg=None, params=None):
        """(cfg, params) of the chip that holds experts `first` ...
        `first + count - 1` of every layer: the same weights, the experts'
        matrices cut to the share."""
        import dataclasses

        import jax

        cfg = dataclasses.replace(cfg or self.cfg,
                                  experts_held=(first, count))
        cut = lambda path, leaf: leaf[first: first + count] if (  # noqa: E731
            path[-1].key in ("w13", "w2")) else leaf
        return cfg, jax.tree_util.tree_map_with_path(
            cut, self.params if params is None else params)

    def make(self, cfg, seed=0):
        """The benchmark's initialiser with the matrices' deviations scaled
        from the published width to this one (by the root of the width each
        matrix sums over), as the dense member's `make` scales its own; the
        router's too, so that its logits have the published widths'
        deviation (1.3) and the three gates are not flat."""
        from benchmarks.families.granite_moe_hybrid import WEIGHTS
        from ray_tpu.models.granite_hybrid import init_params

        wider = (4096 / cfg.d_model) ** 0.5
        return init_params(cfg, _cpu().random.PRNGKey(seed), **_scaled(
            WEIGHTS, dict(
                dict.fromkeys(("in_std", "qkv_std", "router_std",
                               "final_norm"), wider),
                out_std=(8192 / cfg.d_inner) ** 0.5,
                ffn_out_std=(1536 / cfg.d_ff) ** 0.5,
                expert_out_std=(768 / cfg.d_expert) ** 0.5)))

    def _decode(self, model, params, token, state, table, length):
        return super()._decode(model, params, token, state, table,
                               length)[:2]

    def decode_against_reference(self, model, params, seqs, prompt_lens,
                                 steps, rounded=0, fault=None, **sizes):
        """The float32 model as every family's.  The served type routes on
        logits that lie about 1e-3 off the reference's (conv windows and
        K, V in bfloat16), so near a tie it takes another expert, at one
        position in fifty here, and with three of eight experts at gates
        of a third that is a jump of 0.2-2 on a logit which the state
        carries on.  So the served type is held, at EVERY position, to the
        reference UNDER THE SETS THE PROGRAM TOOK (`_telling` hands them
        back; `rounded_logits(chosen=)`), and its sets to the reference's
        margins: an expert the program chose lies no more than SERVED_TIE
        under the reference's third logit of that token.  Returns the
        widest gap."""
        import jax.numpy as jnp
        from benchmarks.reference import granite_moe_hybrid as ref

        if model.cfg.dtype != jnp.bfloat16:
            return super().decode_against_reference(
                model, params, seqs, prompt_lens, steps, rounded, fault,
                **sizes)
        assert not (rounded or fault or sizes)
        prefill, decode = self._telling(model)
        B, L = len(seqs), model.cfg.n_layers
        padded = np.zeros((B, self.BUCKET), np.int32)
        for b, n in enumerate(prompt_lens):
            padded[b, :n] = seqs[b][:n]
        length = jnp.asarray(prompt_lens, jnp.int32)
        (logits, fresh), sets = prefill(params, jnp.asarray(padded),
                                        length - 1)
        state, table = self.paged_state(fresh, B)
        got = [[np.asarray(logits[b])] for b in range(B)]
        # a layer's sets of row b: its prompt's, then a decode step's
        chosen = [[[np.asarray(sets[i]).reshape(B, self.BUCKET, -1)[b, :n]]
                   for i in range(L)] for b, n in enumerate(prompt_lens)]
        for k in range(steps):
            token = jnp.asarray([s[n + k] for s, n in
                                 zip(seqs, prompt_lens)])
            (logits, state), sets = decode(params, token, state, table,
                                           length)
            for b in range(B):
                got[b].append(np.asarray(logits[b]))
                for i in range(L):
                    chosen[b][i].append(np.asarray(sets[i])[b: b + 1])
            length = length + 1
        gaps = []
        for b, n in enumerate(prompt_lens):
            scores: list = []
            want = np.asarray(ref.rounded_logits(
                params, self.SIZES, list(seqs[b][: n + steps]),
                list(range(n - 1, n + steps)), scores=scores,
                chosen=[np.concatenate(c) for c in chosen[b]]))
            gaps.append(np.abs(np.stack(got[b]) - want).max())
            for i, s in enumerate(scores):
                s, took = np.asarray(s), np.concatenate(chosen[b][i])
                third = np.sort(s, -1)[:, -took.shape[1]]
                under = third[:, None] - np.take_along_axis(s, took, -1)
                assert under.max() < self.SERVED_TIE, (b, i, under.max())
        return max(gaps)

    def _telling(self, model):
        """The model's prefill and decode step, jitted, each handing back
        beside its results the sets `route` chose in it, a layer in turn."""
        from unittest import mock

        from ray_tpu.models import granite_hybrid

        def telling(f):
            def run(*args):
                sets, sound = [], granite_hybrid.route

                def route(logits, top_k):
                    idx, gates = sound(logits, top_k)
                    sets.append(idx)
                    return idx, gates

                with mock.patch.object(granite_hybrid, "route", route):
                    return f(*args), sets
            return _cpu().jit(run)

        return telling(lambda p, tokens, last: model.apply(
            p, tokens, last, method=type(model).prefill)[:2]), \
            telling(lambda p, t, s, table, ln: self._decode(
                model, p, t, s, table, ln))


class Lfm2Moe(Family):
    """LFM2-MoE's decoder: d 64, 7 layers (a conv with the dense
    feed-forward, then twice attention, conv, conv with 8 routed experts,
    two a token), 4 query and 2 KV heads of 16, float32."""

    name = "lfm2_moe"
    SIZES = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=64,
        intermediate_size=128,
        layer_types=["conv"] + ["full_attention", "conv", "conv"] * 2,
        max_position_embeddings=256, moe_intermediate_size=32, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=4, num_dense_layers=1,
        num_experts=8, num_experts_per_tok=2, num_hidden_layers=7,
        num_key_value_heads=2,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True, vocab_size=256,
        torch_dtype="float32")

    @functools.cached_property
    def cfg(self):
        from ray_tpu.models.lfm2_moe import TINY_LFM2_MOE

        return TINY_LFM2_MOE

    def check_tiny_configuration(self, family):
        """`family`: the benchmark's `families.lfm2_moe`."""
        from ray_tpu.models.lfm2_moe import count_params

        assert family.program_config(self.SIZES,
                                     attention="reference") == self.cfg
        assert count_params(self.cfg)["total"] == sum(
            x.size for x in _cpu().tree_util.tree_leaves(self.params))

    def make(self, cfg, seed=0):
        """The benchmark's initialiser with the matrices' deviations scaled
        from the published width to this one (sqrt(2048 / 64)), so that
        activations and router logits have the scale they have at the
        published widths.  The embedding keeps its deviation and the final
        norm's scale takes the factor instead: the logits' deviation is the
        published widths' (0.9)."""
        from benchmarks.families.lfm2_moe import WEIGHTS
        from ray_tpu.models.lfm2_moe import init_params

        wider = (2048 / cfg.d_model) ** 0.5
        return init_params(
            cfg, _cpu().random.PRNGKey(seed), **_scaled(WEIGHTS, dict.fromkeys(
                ("in_std", "qkv_std", "out_std", "ffn_out_std",
                 "expert_out_std", "router_std", "final_norm"), wider)))


class MlaMoe(Family):
    """The latent-attention decoder with routed and shared experts: d 64, 3
    layers (a dense one, then two with 8 routed experts, three a token,
    beside 2 shared), 4 heads of 16 + 8 over a latent of 32, float32."""

    name = "mla_moe"
    SIZES = dict(
        vocab_size=256, max_position_embeddings=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, n_shared_experts=2, n_routed_experts=8,
        routed_scaling_factor=2.446, kv_lora_rank=32, q_lora_rank=None,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        topk_method="noaux_tc", n_group=1, topk_group=1,
        num_experts_per_tok=3, moe_layer_freq=1, first_k_dense_replace=1,
        norm_topk_prob=True, scoring_func="sigmoid", num_key_value_heads=4,
        hidden_act="silu", rms_norm_eps=1e-5, rope_theta=800000,
        rope_scaling=None, attention_bias=False, tie_word_embeddings=False,
        torch_dtype="float32")

    @functools.cached_property
    def cfg(self):
        from ray_tpu.models.mla_moe import TINY_MLA_MOE

        return TINY_MLA_MOE

    def check_tiny_configuration(self, family):
        """`family`: the benchmark's `families.mla_moe`."""
        cfg = self.cfg
        assert family.program_config(family.sizes(self.SIZES),
                                     attention="reference") == cfg
        assert cfg.latent_dim == 40 and cfg.latent_row == 128
        assert cfg.d_qk == 24

    def make(self, cfg, seed=0):
        """The benchmark's initialiser with the matrices' deviations scaled
        from the published widths to these (by the root of the width each
        matrix sums over), so that activations, scores and router logits
        have the scale they have there."""
        from benchmarks.families.mla_moe import WEIGHTS
        from ray_tpu.models.mla_moe import init_params

        over_d = (2048 / cfg.d_model) ** 0.5
        over_expert = (1408 / cfg.d_expert) ** 0.5
        return init_params(cfg, _cpu().random.PRNGKey(seed), **_scaled(
            WEIGHTS, dict(
                dict.fromkeys(("in_std", "q_std", "kv_a_std", "router_std",
                               "head_std"), over_d),
                kv_b_std=(512 / cfg.kv_rank) ** 0.5,
                out_std=(2048 / (cfg.n_heads * cfg.d_v)) ** 0.5,
                ffn_out_std=(11264 / cfg.d_ff) ** 0.5,
                expert_out_std=over_expert, shared_out_std=over_expert)))

    def reference(self, params, seq, rows=None, sizes=None, **how):
        from benchmarks.reference import mla_moe as ref

        # (a level's own logits: `ref.logits` hands the harness a level's
        # best token standing over the float32 logits)
        return np.asarray(ref.rounded_logits(params, sizes or self.SIZES,
                                             list(seq), rows, **how))


class MiniCpmSala(Family):
    """MiniCPM-SALA's decoder: d 64, 4 layers (sparse, lightning, lightning,
    sparse), 4 query heads over 2 K/V heads of 16, blocks (pages) of 8
    tokens, compressed keys of 4 tokens every 2, one initial block, a
    window of 2 blocks and the 2 best others kept past `dense_len` 64;
    float32.  Its engine has pages of 8 (`SALA_ENGINE`), so it stands
    beside `FAMILIES`, whose cases share pages of 16."""

    name = "minicpm_sala"
    SIZES = dict(
        attention_bias=False, attn_use_rope=False, head_dim=16,
        hidden_act="silu", hidden_size=64, intermediate_size=128,
        lightning_head_dim=16, lightning_nh=4, lightning_nkv=4,
        lightning_scale="1/sqrt(d)", lightning_use_rope=True,
        max_position_embeddings=512,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"],
        num_attention_heads=4, num_hidden_layers=4, num_key_value_heads=2,
        qk_norm=True, rms_norm_eps=1e-6, vocab_size=256, rope_theta=10000,
        scale_emb=12, scale_depth=1.4, dim_model_base=16,
        tie_word_embeddings=False, use_output_gate=True,
        use_output_norm=True, attn_use_output_gate=True,
        torch_dtype="float32", published_layers=16,
        sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8,
                           topk=2, window_size=16, init_blocks=1,
                           dense_len=64))

    @functools.cached_property
    def cfg(self):
        from ray_tpu.models.minicpm_sala import TINY_SALA

        return TINY_SALA

    def make(self, cfg, seed=0):
        """The benchmark's initialiser with the matrices' deviations scaled
        from the published widths to these (by the root of the width each
        matrix sums over): the mixers and the feed-forwards each write a
        visible share of the stream, and the sparse layers' softmax is as
        sharp as there."""
        from benchmarks.families.minicpm_sala import WEIGHTS
        from ray_tpu.models.minicpm_sala import init_params

        over_d = (4096 / cfg.d_model) ** 0.5
        # (both sparse layers' W_o at 0.025: the benchmark's (0.0025, 0.04)
        # are for what bfloat16 pages and seven layers make of a block
        # exchanged at a near-tie there; float32 against float32 exchanges
        # none here, and these tests' streams were chosen at 0.025)
        weights = dict(WEIGHTS, sparse_out_std=0.025)
        return init_params(cfg, _cpu().random.PRNGKey(seed), **_scaled(
            weights, dict(
                dict.fromkeys(("in_std", "sparse_out_std",
                               "lightning_out_std"), over_d),
                ffn_out_std=(16384 / cfg.d_ff) ** 0.5,
                # (the head reads the stream over d / dim_model_base: 16
                # there, 4 here)
                head_std=over_d * (cfg.d_model / cfg.dim_model_base) / 16)))


SALA_ENGINE = dict(max_batch=3, max_len=160, page_size=8, decode_chunk=4)

dense, sambay, granite_hybrid, lfm2_moe, mla_moe, granite_moe_hybrid = \
    Dense(), SambaY(), GraniteHybrid(), Lfm2Moe(), MlaMoe(), \
    GraniteMoeHybrid()
FAMILIES = {f.name: f for f in (dense, sambay, granite_hybrid, lfm2_moe,
                                mla_moe, granite_moe_hybrid)}
minicpm_sala = MiniCpmSala()
