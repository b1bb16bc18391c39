"""ray_tpu CLI.

Parity: reference python/ray/scripts/scripts.py (`ray start/stop/status`,
`ray list ...` at :2441-2492, `ray microbenchmark`). Run as
`python -m ray_tpu.scripts <cmd>`.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def cmd_start(args):
    from ray_tpu._private.accelerator import node_resources_and_labels
    from ray_tpu._private.config import Config
    from ray_tpu._private.node import RuntimeNode

    cfg = Config()
    node = RuntimeNode(cfg)
    resources, labels = node_resources_and_labels()
    if args.resources:
        resources.update(json.loads(args.resources))
    if args.num_cpus is not None:
        resources["CPU"] = args.num_cpus
    if args.head:
        host, port = node.start_gcs()
        handle = node.start_raylet(resources=resources or None, labels=labels,
                                   is_head=True)
        info = {"gcs_address": f"{host}:{port}",
                "raylet": f"{handle.host}:{handle.port}",
                "node_id": handle.node_id,
                "store_path": handle.store_path,
                "session_dir": node.session_dir}
        if getattr(args, "client_server_port", None) is not None:
            # Host a client proxy in the head supervisor (reference:
            # `ray start --head --ray-client-server-port`).
            import ray_tpu
            from ray_tpu.util.client.server import serve as client_serve

            ray_tpu.init(address=f"{host}:{port}",
                         _head_raylet=(handle.host, handle.port),
                         _store_path=handle.store_path,
                         _node_id=handle.node_id)
            cs = client_serve(port=args.client_server_port)
            info["client_server"] = f"{cs.host}:{cs.port}"
        with open(args.state_file, "w") as f:
            json.dump(info, f)
        print(json.dumps(info))
        print(f"\nhead started; connect with:\n  ray_tpu.init("
              f"address='{host}:{port}', ...)\nstate written to "
              f"{args.state_file}; `ray_tpu stop` to shut down")
    else:
        if not args.address:
            print("worker nodes need --address=<gcs host:port>", file=sys.stderr)
            return 1
        host, port = args.address.rsplit(":", 1)
        node.attach_gcs(host, int(port))
        handle = node.start_raylet(resources=resources or None, labels=labels)
        print(json.dumps({"node_id": handle.node_id,
                          "raylet": f"{handle.host}:{handle.port}"}))
    # Keep the daemon processes alive under this supervisor.
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    finally:
        node.shutdown()
    return 0


def cmd_stop(args):
    if os.path.exists(args.state_file):
        os.unlink(args.state_file)
    os.system("pkill -f 'ray_tpu._private.(gcs|raylet|worker)' 2>/dev/null")
    print("stopped ray_tpu daemons")
    return 0


def _connect_from_state(args):
    import ray_tpu

    if ray_tpu.is_initialized():
        # In-process use (tests, embedding): the session is the
        # CALLER's; _shutdown_if_owned leaves it alone.
        ray_tpu._cli_owns_session = False
        return ray_tpu
    with open(args.state_file) as f:
        info = json.load(f)
    host, port = info["raylet"].rsplit(":", 1)
    ray_tpu.init(address=info["gcs_address"],
                 _head_raylet=(host, int(port)),
                 _store_path=info["store_path"],
                 _node_id=info["node_id"])
    ray_tpu._cli_owns_session = True
    return ray_tpu


def _shutdown_if_owned(ray_tpu):
    """Tear down only sessions THIS command created — never a live
    session an embedding caller handed us via an early-initialized
    runtime."""
    if getattr(ray_tpu, "_cli_owns_session", True):
        ray_tpu.shutdown()


def cmd_status(args):
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    st = state.cluster_status()
    print(json.dumps(st, indent=2, default=str))
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_serve(args):
    """Declarative serve management (reference: `serve deploy/status`)."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu import serve

    if args.serve_cmd == "deploy":
        from ray_tpu.serve.config_deploy import deploy_config

        handles = deploy_config(args.config)
        print(json.dumps({"deployed": sorted(handles)}))
    elif args.serve_cmd == "status":
        print(json.dumps(serve.status(), indent=2, default=str))
    elif args.serve_cmd == "shutdown":
        serve.shutdown()
        print("serve shut down")
    elif args.serve_cmd == "run":
        # `serve run module:attr` (reference: the serve CLI's main dev
        # entry) — import the deployment (or bound app), deploy, block.
        import importlib

        mod_name, _, attr = args.target.partition(":")
        if not attr:
            print("target must be module:deployment", file=sys.stderr)
            return 1
        sys.path.insert(0, os.getcwd())
        target = getattr(importlib.import_module(mod_name), attr)
        handle = serve.run(target)
        st = serve.status()
        print(json.dumps({"running": sorted(st.get("deployments", st))},
                         default=str), flush=True)
        if not getattr(args, "non_blocking", False):
            try:
                signal.pause()
            except KeyboardInterrupt:
                pass
            serve.shutdown()
        del handle
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_stack(args):
    """Dump every worker's thread stacks (reference: `ray stack`)."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    for node in state.dump_stacks():
        print(f"=== node {node.get('node_id', '?')[:12]} ===")
        if "error" in node:
            print(f"  unreachable: {node['error']}")
            continue
        for w in node.get("workers", []):
            hdr = (f"-- worker {w.get('worker_id', '?')[:12]} "
                   f"pid={w.get('pid')} actor={w.get('actor_id')}")
            print(hdr)
            for t in w.get("threads", []):
                print(f"  [{t['thread']}{' daemon' if t['daemon'] else ''}]")
                for line in t["stack"].rstrip().splitlines():
                    print(f"    {line}")
            if "error" in w:
                print(f"  error: {w['error']}")
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_list(args):
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    fn = {"nodes": state.list_nodes, "actors": state.list_actors,
          "jobs": state.list_jobs, "tasks": state.list_tasks,
          "placement-groups": state.list_placement_groups,
          "objects": state.list_objects}[args.entity]
    print(json.dumps(fn(), indent=2, default=str))
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_events(args):
    """`ray_tpu events` — merged structured cluster events (parity:
    reference src/ray/util/event.h + dashboard event module)."""
    import glob
    import os

    from ray_tpu.util.events import list_events

    base = "/tmp/ray_tpu_sessions"
    sessions = sorted(glob.glob(os.path.join(base, "session-*")),
                      key=os.path.getmtime)
    if not sessions:
        print("no sessions found")
        return 1
    for e in list_events(sessions[-1], min_severity=args.severity):
        fields = e.get("fields") or {}
        extra = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f'{e["ts"]:.3f} {e["severity"]:7} {e["source"]:8} '
              f'{e["message"]} {extra}'.rstrip())
    return 0


def cmd_summary(args):
    """`ray_tpu summary tasks|actors|objects` (parity: reference
    `ray summary` — experimental/state/state_cli.py summary commands)."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    fn = {"tasks": state.summarize_tasks, "actors": state.summarize_actors,
          "objects": state.summarize_objects}[args.entity]
    print(json.dumps(fn(), indent=2, default=str))
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_task_latency(args):
    """`ray_tpu task-latency` — per-stage lifecycle latency percentiles
    (SUBMITTED → LEASE_REQUESTED → LEASE_GRANTED → DISPATCHED →
    ARGS_FETCHED → RUNNING → FINISHED/FAILED) from the GCS task-event
    table, rendered as one row per stage."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    out = state.summarize_task_latency(limit=args.limit)
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        print(f"{out['tasks']} tasks with recorded events")
        print(f"{'STAGE':<24}{'COUNT':>8}{'P50':>10}{'P95':>10}"
              f"{'P99':>10}{'MEAN':>10}{'MAX':>10}  (ms)")
        for name, _, _ in state.LATENCY_STAGES:
            s = out["stages"].get(name)
            if s is None:
                continue
            print(f"{name:<24}{s['count']:>8}{s['p50_ms']:>10.2f}"
                  f"{s['p95_ms']:>10.2f}{s['p99_ms']:>10.2f}"
                  f"{s['mean_ms']:>10.2f}{s['max_ms']:>10.2f}")
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_pump_stats(args):
    """`ray_tpu pump-stats` — daemon event-loop stats: per-handler call
    counts and latencies for the GCS and every raylet pump (analogue of
    the reference's event_stats.h debug dump)."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    print(json.dumps(state.pump_stats(), indent=2, default=str))
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_drain(args):
    """`ray_tpu drain <node_id> [--reason r] [--deadline s] [--no-wait]`
    — graceful evacuation (parity: reference `ray drain-node` /
    autoscaler.proto DrainNode): the raylet re-spills queued leases,
    waits for running work up to the deadline, pushes primary object
    copies and pinned device objects to peers, while the GCS migrates
    restartable actors. By default waits until the node reports
    DRAINED (then it is safe to terminate)."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu._private.api_internal import get_core_worker

    cw = get_core_worker()
    resp = cw._run(cw.gcs.call("DrainNode", {
        "node_id": args.node_id, "reason": args.reason,
        "deadline_s": args.deadline}, timeout=60))
    if not isinstance(resp, dict):
        resp = {"ok": resp}
    if not resp.get("ok"):
        print(json.dumps(resp))
        _shutdown_if_owned(ray_tpu)
        return 1
    rc = 0
    if not args.no_wait:
        from ray_tpu._private.common import wait_for_drained

        outcome, me = wait_for_drained(
            lambda: cw._run(cw.gcs.call("GetAllNodes", {}))["nodes"],
            args.node_id, args.deadline, slack_s=15.0)
        resp["state"] = "DRAINED" if outcome == "DRAINED" \
            else (me.get("state", outcome) if me else outcome)
        if me is not None:
            resp["drain_stats"] = me.get("drain_stats") or {}
        if outcome != "DRAINED":
            rc = 1
    print(json.dumps(resp))
    _shutdown_if_owned(ray_tpu)
    return rc


def cmd_memory(args):
    """`ray_tpu memory` — cluster object-memory report (parity:
    reference `ray memory` / memory_utils.py: per-node store usage +
    this driver's owned references with pinned sizes and totals)."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    nodes = state.node_stats()
    print(f"{'NODE':<10}{'IN USE':>12}{'HEAP':>12}{'OBJECTS':>9}"
          f"{'EVICTED':>9}{'SPILLED':>12}")
    tot_use = tot_heap = 0
    for n in nodes:
        st = n.get("store", {})
        tot_use += st.get("bytes_in_use", 0)
        tot_heap += st.get("heap_size", 0)
        print(f"{n.get('node_id', '?')[:8]:<10}"
              f"{st.get('bytes_in_use', 0) / 2**20:>10.1f}MB"
              f"{st.get('heap_size', 0) / 2**20:>10.1f}MB"
              f"{st.get('num_objects', 0):>9}"
              f"{st.get('num_evictions', 0):>9}"
              f"{n.get('spilled_bytes', 0) / 2**20:>10.1f}MB")
    print(f"{'TOTAL':<10}{tot_use / 2**20:>10.1f}MB"
          f"{tot_heap / 2**20:>10.1f}MB\n")
    objs = state.list_objects()
    objs.sort(key=lambda o: -(o.get("size") or 0))
    print(f"owned by this driver: {len(objs)} refs, "
          f"{sum(o.get('size') or 0 for o in objs) / 2**20:.1f}MB")
    print(f"{'OBJECT':<14}{'STATE':<9}{'SIZE':>10}{'LREF':>6}{'SREF':>6}"
          f"  LOCATIONS")
    for o in objs[:args.limit]:
        print(f"{o['object_id'][:12]:<14}{o['state']:<9}"
              f"{(o.get('size') or 0) / 2**10:>8.1f}KB"
              f"{o['local_refs']:>6}{o['submitted_refs']:>6}"
              f"  {','.join(n[:8] for n in o.get('locations', [])) or '-'}")
    if len(objs) > args.limit:
        print(f"... {len(objs) - args.limit} more (use --limit)")
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_device_objects(args):
    """`ray_tpu device-objects` — device object plane report: pinned-HBM
    bytes/objects per worker (raylet fan-out), transfer/fallback route
    counters, and this driver's owned device-object descriptors."""
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util import state

    out = state.list_device_objects(entries=not args.no_entries)
    if args.json:
        print(json.dumps(out, indent=2, default=str))
        _shutdown_if_owned(ray_tpu)
        return 0
    c = out["local"]["counters"]
    print(f"routes: in_process={c['in_process']} "
          f"collective={c['collective']} "
          f"host_fallback={c['host_fallback']} lost={c['lost']} "
          f"released={c['released']}")
    print(f"{'NODE':<10}{'WORKER':<10}{'PINNED':>8}{'BYTES':>12}"
          f"{'IN-PROC':>9}{'COLL':>6}{'HOST':>6}")
    for node in out["nodes"]:
        nid = str(node.get("node_id", "?"))[:8]
        if "error" in node:
            print(f"{nid:<10}unreachable: {node['error']}")
            continue
        for w in node.get("workers", []):
            wc = w.get("counters", {})
            print(f"{nid:<10}{str(w.get('worker_id', '?'))[:8]:<10}"
                  f"{w.get('pinned_objects', 0):>8}"
                  f"{w.get('pinned_bytes', 0) / 2**20:>10.2f}MB"
                  f"{wc.get('in_process', 0):>9}"
                  f"{wc.get('collective', 0):>6}"
                  f"{wc.get('host_fallback', 0):>6}")
    if out["owned"]:
        print(f"\nowned device objects: {len(out['owned'])}")
        print(f"{'OBJECT':<14}{'STATE':<8}{'LEAVES':>7}{'BYTES':>12}"
              f"  PIN WORKER")
        for o in out["owned"]:
            print(f"{o['object_id'][:12]:<14}{o['state']:<8}"
                  f"{o['leaves']:>7}{o['pinned_bytes'] / 2**10:>10.1f}KB"
                  f"  {o['pin_worker']}")
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_microbenchmark(args):
    from ray_tpu import microbenchmark

    microbenchmark.main()
    return 0


def cmd_dashboard(args):
    ray_tpu = _connect_from_state(args)
    from ray_tpu import dashboard

    port = dashboard.start(port=args.port)
    print(f"dashboard at http://127.0.0.1:{port}/")
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    dashboard.stop()
    _shutdown_if_owned(ray_tpu)
    return 0


def cmd_job(args):
    ray_tpu = _connect_from_state(args)
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    try:
        if args.job_cmd == "submit":
            sid = client.submit_job(entrypoint=" ".join(args.entrypoint))
            print(sid)
            if args.wait:
                status = client.wait_until_finished(sid, timeout=args.timeout)
                print(status)
                print(client.get_job_logs(sid), end="")
                return 0 if status == "SUCCEEDED" else 1
        elif args.job_cmd == "status":
            print(client.get_job_status(args.id))
        elif args.job_cmd == "logs":
            print(client.get_job_logs(args.id), end="")
        elif args.job_cmd == "list":
            for j in client.list_jobs():
                print(json.dumps(j.__dict__, default=str))
        elif args.job_cmd == "stop":
            print("stopped" if client.stop_job(args.id) else "not running")
    finally:
        _shutdown_if_owned(ray_tpu)
    return 0


def cmd_timeline(args):
    if args.chip is not None:
        # A session's span files are read where they lie: no cluster is
        # joined, and the session may be one that has ended.
        from ray_tpu.util.timeline import chip_report

        session_dir = args.chip or os.environ.get("RAY_TPU_SESSION_DIR")
        if not session_dir:  # a head started by `ray_tpu start`
            with open(args.state_file) as f:
                session_dir = json.load(f)["session_dir"]
        print(chip_report(session_dir))
        return 0
    ray_tpu = _connect_from_state(args)
    from ray_tpu.util.timeline import dump_timeline

    session_dir = None
    if ray_tpu._cli_owns_session:  # a head started by `ray_tpu start`
        with open(args.state_file) as f:
            session_dir = json.load(f).get("session_dir")
    path = dump_timeline(args.output, session_dir=session_dir)
    print(f"chrome trace written to {path} (open in chrome://tracing "
          "or https://ui.perfetto.dev)")
    _shutdown_if_owned(ray_tpu)
    return 0


def main():
    parser = argparse.ArgumentParser(prog="ray_tpu")
    parser.add_argument("--state-file", default="/tmp/ray_tpu_head.json")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start a head or worker node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", default="")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--resources", default="")
    p.add_argument("--client-server-port", type=int, default=None,
                   help="serve remote client:// drivers on this port "
                        "(reference: --ray-client-server-port)")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop local daemons")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("stack", help="dump all workers' thread stacks")
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser("status", help="cluster status")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("list", help="list cluster entities")
    p.add_argument("entity", choices=["nodes", "actors", "jobs", "tasks",
                                      "placement-groups", "objects"])
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("events", help="structured cluster events "
                                      "(node/actor deaths, OOM, spills)")
    p.add_argument("--severity", default="INFO",
                   choices=["DEBUG", "INFO", "WARNING", "ERROR", "FATAL"])
    p.set_defaults(fn=cmd_events)

    p = sub.add_parser("summary", help="aggregate counts per entity "
                                       "(parity: `ray summary`)")
    p.add_argument("entity", choices=["tasks", "actors", "objects"])
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser("task-latency", help="per-stage task lifecycle "
                                            "latency percentiles")
    p.add_argument("--limit", type=int, default=200000)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_task_latency)

    p = sub.add_parser("pump-stats", help="daemon event-loop stats "
                                          "(per-handler counts/latencies)")
    p.set_defaults(fn=cmd_pump_stats)

    p = sub.add_parser("drain", help="gracefully drain a node: evacuate "
                                     "leases, actors, objects, and pinned "
                                     "HBM, then wait for DRAINED (parity: "
                                     "`ray drain-node`)")
    p.add_argument("node_id")
    p.add_argument("--reason", default="manual",
                   choices=["preemption", "idle", "manual"])
    p.add_argument("--deadline", type=float, default=30.0,
                   help="seconds the raylet may spend evacuating")
    p.add_argument("--no-wait", action="store_true",
                   help="return after initiating the drain instead of "
                        "waiting for DRAINED")
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser("memory", help="cluster object-memory report "
                                      "(parity: `ray memory`)")
    p.add_argument("--limit", type=int, default=20)
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser("device-objects",
                       help="device object plane report (pinned-HBM "
                            "bytes, transfer routes, descriptors)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--no-entries", action="store_true",
                   help="skip per-array registry entries")
    p.set_defaults(fn=cmd_device_objects)

    p = sub.add_parser("microbenchmark", help="core-runtime throughput suite")
    p.set_defaults(fn=cmd_microbenchmark)

    p = sub.add_parser("dashboard", help="serve the web dashboard")
    p.add_argument("--port", type=int, default=8265)
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("serve", help="declarative serve deploy/status")
    ssub = p.add_subparsers(dest="serve_cmd", required=True)
    ps = ssub.add_parser("deploy")
    ps.add_argument("config", help="JSON config file (ServeDeploy schema)")
    ssub.add_parser("status")
    ssub.add_parser("shutdown")
    pr = ssub.add_parser("run", help="import module:deployment, deploy, "
                                     "block (reference: `serve run`)")
    pr.add_argument("target")
    pr.add_argument("--non-blocking", action="store_true",
                    dest="non_blocking")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("job", help="submit and manage jobs")
    jsub = p.add_subparsers(dest="job_cmd", required=True)
    pj = jsub.add_parser("submit")
    pj.add_argument("entrypoint", nargs="+")
    pj.add_argument("--wait", action="store_true")
    pj.add_argument("--timeout", type=float, default=300.0)
    for name in ("status", "logs", "stop"):
        pj = jsub.add_parser(name)
        pj.add_argument("id")
    jsub.add_parser("list")
    p.set_defaults(fn=cmd_job)

    p = sub.add_parser("timeline", help="dump chrome-trace of task events")
    p.add_argument("--output", default="/tmp/ray_tpu_timeline.json")
    p.add_argument("--chip", nargs="?", metavar="SESSION_DIR", default=None,
                   const="",
                   help="print the chip's ledger of a session (its "
                        "`chip.program` spans) instead: chip seconds by "
                        "kind, starved seconds by what the loop was doing, "
                        "the longest starved intervals, the watcher's "
                        "lateness, the programs longest over their like; "
                        "SESSION_DIR: a session's directory "
                        "(this head's where left out)")
    p.set_defaults(fn=cmd_timeline)

    args = parser.parse_args()
    sys.exit(args.fn(args) or 0)


if __name__ == "__main__":
    main()
