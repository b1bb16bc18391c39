"""Find a cell's files by the names `BENCHMARK.json` gives: no registry.

A cell names a configuration and a traffic mix.  The configuration's file
is the `file` of its `configs` entry; the mix is `traffic/<traffic>.json`;
its `kind` names `drivers/<kind>.py`; each per-layer metric the cell
reports is read by `layer_metrics/<metric>.py`; the configuration's
`family` (`dense_decoder` where the file names none) is
`families/<family>.py`, which says how the file's sizes become the
program's model and which plain reference it is held to.  Every directory
in `paths` is searched, then this harness's own, so a later PR (or a test)
adds a cell or a family by adding files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

HARNESS_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(HARNESS_ROOT)
DEFAULT_FAMILY = "dense_decoder"


class BenchmarkError(RuntimeError):
    """The benchmark cannot run or its result cannot be trusted."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    family: ModuleType    # families/<family>.py; its name and `root` cross
    root: str             # to the lease-holder, which loads it again
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    readers: dict         # per-layer metric name -> module with read(obs)

    @property
    def family_name(self) -> str:
        return self.config.get("family", DEFAULT_FAMILY)


def load_benchmark(root: str = REPO_ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _search_dirs(bench: dict, root: str) -> list:
    dirs = [os.path.join(root, p) for p in bench["paths"]]
    if HARNESS_ROOT not in dirs:
        dirs.append(HARNESS_ROOT)
    return dirs


def find_file(bench: dict, root: str, sub: str, filename: str) -> str:
    tried = []
    for d in _search_dirs(bench, root):
        path = os.path.join(d, sub, filename)
        tried.append(path)
        if os.path.isfile(path):
            return path
    raise BenchmarkError(f"no {sub}/{filename}: looked for {tried}")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _modname(kind: str, name: str) -> str:
    return f"_bench_{kind}_" + name.replace(".", "_").replace("-", "_")


def _load_module(path: str, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def sibling_reader(here: str, name: str) -> ModuleType:
    """The reader of metric `name` that lies beside the file `here`: for a
    metric that is another's quantity under another `moves` (a cell that
    reports other end-to-end metrics needs its own per-layer entries)."""
    return _load_module(
        os.path.join(os.path.dirname(os.path.abspath(here)), name + ".py"),
        _modname("metric", name))


def beside(here: str, sub: str, filename: str) -> ModuleType:
    """The module `<sub>/<filename>` of the directory of `paths` that the
    file `here` lies in (one level down): how a family that a later PR
    adds finds the plain reference it brought with it."""
    top = os.path.dirname(os.path.dirname(os.path.abspath(here)))
    return _load_module(os.path.join(top, sub, filename),
                        _modname(sub, os.path.splitext(filename)[0]))


def load_family(name: str, root: str = REPO_ROOT,
                bench: dict | None = None) -> ModuleType:
    """`families/<name>.py`, found as drivers and readers are."""
    return _load_module(
        find_file(bench or load_benchmark(root), root, "families",
                  name + ".py"),
        _modname("family", name))


def check_configuration(conf: dict, family: ModuleType) -> None:
    """What the `model-configs` guide asks of any configuration file,
    whatever its family; then the family's own `check_file`.  A family
    says which keys may be cut (`REDUCIBLE`) and, where it has them,
    which key counts the experts held (`EXPERTS_KEY`), the rows of the
    vocabulary (`VOCAB_KEY`, `vocab_size`), and how to read the layer
    pattern (`layer_pattern(conf)` -> leading dense layers, period)."""
    def refuse(why):
        raise BenchmarkError(f"configuration {conf.get('source')}: {why}")

    for key in ("source", "reduced", "assumed", "deployment", "memory"):
        if key not in conf:
            refuse(f"no {key!r}")
    if not conf["memory"]:
        refuse("the compile's memory report is not recorded")
    depth = getattr(family, "DEPTH_KEY", "num_hidden_layers")
    experts = getattr(family, "EXPERTS_KEY", None)
    vocab = getattr(family, "VOCAB_KEY", "vocab_size")
    for key in conf["reduced"]:
        # What is cut is named with its published value; no width is.
        if key not in family.REDUCIBLE:
            refuse(f"{key!r} is cut and the family lets only "
                   f"{sorted(family.REDUCIBLE)} be")
        if not conf.get("published", {}).get(key, 0) > conf[key]:
            refuse(f"{key!r} is listed as cut but is not under its "
                   "published value")
    pattern = getattr(family, "layer_pattern", lambda conf: None)(conf)
    if pattern is not None and depth in conf["reduced"]:
        leading, period = pattern
        if conf[depth] - leading < max(period, 4):
            refuse(f"{conf[depth]} layers keep no whole period of "
                   f"{period} and four layers after the {leading} "
                   "leading dense ones")
    if experts in conf["reduced"] and conf[experts] < 8:
        refuse(f"{conf[experts]} experts held; the floor is 8")
    if vocab in conf["reduced"] and \
            conf[vocab] * 8 < conf["published"][vocab]:
        refuse("the vocabulary's slice is under an eighth of the "
               "published one")
    if set(conf["reduced"]) - {depth} and \
            "chips_sharing_a_layer" not in conf["deployment"]:
        refuse("a cut beyond depth is a chip's share of a deployment: "
               "`deployment.chips_sharing_a_layer` says of which")
    family.check_file(conf)


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = REPO_ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {name!r} names configuration "
                             f"{entry['config']!r}, which `configs` lacks")
    cfg_path = os.path.join(root, cfg_entry["file"])
    if not os.path.isfile(cfg_path):
        raise BenchmarkError(f"no configuration file {cfg_path}")
    config = _load_json(cfg_path)
    traffic = _load_json(find_file(bench, root, "traffic",
                                   entry["traffic"] + ".json"))
    kind = traffic["kind"]
    driver = _load_module(find_file(bench, root, "drivers", kind + ".py"),
                          _modname("driver", kind))
    family = load_family(config.get("family", DEFAULT_FAMILY), root, bench)
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    readers = {
        m["name"]: _load_module(
            find_file(bench, root, "layer_metrics", m["name"] + ".py"),
            _modname("metric", m["name"]))
        for m in per_layer}
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, driver=driver, family=family, root=root,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=per_layer, readers=readers)


def read_layer_metrics(cell: Cell, obs: dict) -> dict:
    """Each reader takes its number from the observations; one that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
