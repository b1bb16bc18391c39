"""train: host time of a step outside the compiled call -- taking the next
batch from the prefetcher and `train.report`; the median over the steps."""

from benchmarks.harness import stats

LAYER = "train"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(obs):
    steps = obs.get("steps")
    if not steps:
        return None
    return stats.median([(s["reported"] - s["done"])
                         + (s["dispatch"] - s["start"]) for s in steps]) * 1e3
