"""runtime: `serve.run` / `fit` called -> the lease-holder answers with its
device report (lease, worker start, platform pin, chip open)."""

LAYER = "runtime"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    return obs.get("worker_ready_s")
