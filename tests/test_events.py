"""Structured event framework (parity: reference src/ray/util/event.h +
dashboard event module)."""


import ray_tpu
from ray_tpu.util.events import configure, list_events, record


def test_record_and_list(tmp_path):
    configure(str(tmp_path), "unit")
    record("INFO", "test", "hello", a=1)
    record("ERROR", "test", "boom")
    record("DEBUG", "other", "noise")
    evts = list_events(str(tmp_path))
    assert [e["message"] for e in evts] == ["hello", "boom", "noise"]
    errs = list_events(str(tmp_path), min_severity="ERROR")
    assert [e["message"] for e in errs] == ["boom"]
    assert evts[0]["fields"] == {"a": 1}
    only = list_events(str(tmp_path), source="other")
    assert [e["message"] for e in only] == ["noise"]


def test_daemons_emit_lifecycle_events(ray_start_regular):
    @ray_tpu.remote
    def ping():
        return 1

    assert ray_tpu.get(ping.remote()) == 1
    # This cluster's own session: the newest directory under the temp
    # dir is another xdist worker's as often as not.
    evts = list_events(ray_tpu._runtime_node.session_dir)
    messages = {e["message"] for e in evts}
    assert "node started" in messages  # raylet boot event
    sources = {e["source"] for e in evts}
    assert "raylet" in sources
