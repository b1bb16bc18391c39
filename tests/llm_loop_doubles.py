"""What `test_llm_overlap.py` and `test_llm_hand_off.py` share: the double
that stands where the engine calls its decode program and holds a chunk's
completion until the test lets go (so "while the chunk is on the chip" is a
state the test is in, not a race it hopes to win), the engine under it, and
the two tiny models (`tests/tiny_families.py`: a dense one, whose steps can
be run again, and a hybrid one, whose cannot).  A test file imports the
fixtures it uses BY NAME.  This module holds no test.
"""

import threading

import numpy as np
import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.util import tracing
from tests import tiny_families
from tests.tiny_families import LOOP_ENGINE as ENGINE, LOOP_K as K  # noqa: F401

# An event that has not come after this long has failed the test.
WAIT = 120.0
MODELS = {"dense": tiny_families.dense,
          "hybrid": tiny_families.granite_hybrid}


@pytest.fixture(params=list(MODELS))
def model(request):
    """A tiny model (`cfg`, `params`, `rewinds`), and whether a stream is
    what its plain reference decodes greedily from the prompt."""
    return MODELS[request.param]


@pytest.fixture
def dense():
    return tiny_families.dense


def _prompt(seed, n):
    return tiny_families.prompts(seed, (n,), vocab=120)[0]


class Held:
    """A chunk's tokens whose completion the test holds: the watcher's
    `block_until_ready` and the loop's fetch wait for the gate, and raise
    what the test planted."""

    def __init__(self, toks, gate, hold):
        self._toks, self._gate, self._hold = toks, gate, hold
        self.watched = threading.Event()    # the loop waits behind it

    def _wait(self):
        self.watched.set()
        assert self._gate.wait(WAIT)
        if self._hold.error is not None:
            raise self._hold.error

    def block_until_ready(self):
        self._wait()
        return self

    def __array__(self, *args, **kwargs):
        self._wait()
        return np.asarray(self._toks)


class Hold:
    """Stands where the engine calls its decode program (and where it
    prefills a group of admissions): counts the chunks, keeps each one's
    `lens` and every watched stream's backlog at its dispatch, and holds
    the completion of the chunks the test asks for."""

    def __init__(self, eng):
        self.eng, self.real = eng, eng._decode_chunk_paged
        self.streams: list = []
        self.calls, self.lens, self.backlogs = 0, [], []
        self.gates, self.error, self.held_call = [], None, 0
        self._armed = None
        self.dispatched = threading.Event()     # the held chunk is on
        self.prefilled = threading.Event()      # a group was dispatched
        self._seen = {s["id"] for s in tracing.recent_spans()}
        eng._decode_chunk_paged = self
        admit_group = eng._admit_paged_group

        def admit(*args):
            admit_group(*args)
            self.prefilled.set()

        eng._admit_paged_group = admit
        # (asked for by a test: a prefill's first tokens held as well)
        self.prefills_held: list = []
        self.hold_prefills = False
        real_sample = eng._sample

        def sample(*args):
            toks = real_sample(*args)
            if not self.hold_prefills:
                return toks
            self.gates.append(threading.Event())
            self.prefills_held.append(Held(toks, self.gates[-1], self))
            return self.prefills_held[-1]

        eng._sample = sample

    def __call__(self, *args):
        self.calls += 1
        self.lens.append(np.asarray(args[5]).copy())
        self.backlogs.append([len(h._handed) + len(h._booked)
                              for h in self.streams])
        out = self.real(*args)
        if self._armed is None:
            return out
        gate, self._armed, self.held_call = self._armed, None, self.calls
        self.dispatched.set()
        return (Held(out[0], gate, self), *out[1:])

    def arm(self):
        """The next chunk dispatched stays on the chip until its gate
        (returned) is set, or `release`."""
        gate = threading.Event()
        self.gates.append(gate)
        self.dispatched.clear()
        self.prefilled.clear()
        self._armed = gate
        return gate

    def release(self, error=None):
        self.error = error
        for gate in self.gates:
            gate.set()

    def held(self):
        return any(not gate.is_set() for gate in self.gates)

    def spans(self, name=None):
        return [s for s in tracing.recent_spans()
                if s["id"] not in self._seen
                and (name is None or s["name"] == name)]


def _end(span):
    return span["t0_ns"] + span["dur_ns"]


def _with_its_first_chunk_held(eng, hold, prompt, max_new):
    """Submit a stream whose first chunk stays on the chip: (handle, its
    iterator, its first token).  The first token is handed over after that
    chunk's dispatch, so when it is here the chunk is on, and held: no
    race between the test and the loop."""
    hold.arm()
    handle = eng.submit(prompt, SamplingParams(max_new_tokens=max_new))
    stream = iter(handle)
    head = [next(stream)]
    assert hold.dispatched.is_set() and hold.held()
    hold.prefilled.clear()              # (set by this stream's own group)
    return handle, stream, head


@pytest.fixture
def engine(model):
    made = []

    def make(**over):
        made.append(LLMEngine(model.cfg, model.params,
                              **dict(ENGINE, **over)))
        return made[-1]

    yield make
    for eng in made:
        if "hold" in eng.__dict__:
            eng.hold.release()
        eng.shutdown()


def _held(engine, **over):
    eng = engine(**over)
    eng.hold = Hold(eng)
    return eng, eng.hold
