"""ray-tpu CLI.

Analog of the reference's python/ray/scripts/scripts.py subset
(`ray status/memory/timeline/list`, scripts.py:529,2390-2403) plus
`bench`. argparse instead of click (no extra deps); single-node commands
initialize a local runtime on demand.
"""

from __future__ import annotations

import argparse
import json
import sys


def _ensure_init():
    import ray_tpu
    if not ray_tpu.is_initialized():
        ray_tpu.init()


def cmd_status(args) -> int:
    _ensure_init()
    from ray_tpu._private.state import status_summary
    print(status_summary())
    return 0


def cmd_memory(args) -> int:
    _ensure_init()
    from ray_tpu._private.state import memory_summary
    print(memory_summary())
    return 0


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024.0
    return f"{n:.1f}TiB"


def _render_top_frame(snap: dict) -> str:
    """One `ray-tpu top` frame from runtime.top_snapshot(): every number
    is a windowed derivation from the head's time-series store."""
    lines = []
    tasks = snap.get("tasks", {})
    objects = snap.get("objects", {})
    ts_meta = snap.get("timeseries", {})
    lines.append(
        f"ray-tpu top — window {snap.get('window_s', 0):g}s — "
        f"{len(snap.get('nodes', []))} node(s) — "
        f"{ts_meta.get('series', 0)} series "
        f"({ts_meta.get('dropped_series', 0)} dropped)")
    alerts = snap.get("alerts", {})
    if alerts.get("firing_count"):
        rules = ", ".join(sorted(set(alerts.get("rules", []))))
        lines.append(f"ALERTS FIRING: {alerts['firing_count']} ({rules})")
    lines.append(
        f"tasks/s  submitted {tasks.get('submitted_per_s', 0.0):.2f}  "
        f"finished {tasks.get('finished_per_s', 0.0):.2f}  "
        f"failed {tasks.get('failed_per_s', 0.0):.2f}")
    lines.append(
        f"objects  store {_fmt_bytes(objects.get('store_bytes'))}  "
        f"spill/s {_fmt_bytes(objects.get('spill_bytes_per_s'))}  "
        f"restores/s {objects.get('restores_per_s', 0.0):.2f}")
    xfer = snap.get("transfer") or {}
    if xfer.get("links_active"):
        top_link = xfer.get("top_link") or {}
        line = (f"transfer {xfer.get('mbps_total', 0.0):.2f}MB/s over "
                f"{xfer['links_active']} link(s)")
        if top_link:
            line += (f"  top {top_link.get('src', '')[:12]}->"
                     f"{top_link.get('dst', '')[:12]} "
                     f"{top_link.get('mbps', 0.0):.2f}MB/s")
        hot = xfer.get("max_fanout") or {}
        if hot:
            line += (f"  fanout {hot.get('key', '')[:16]} x"
                     f"{hot.get('fanout', 0)}")
        lines.append(line)
    loops = snap.get("loops", {})
    if loops:
        lines.append("loop lag  " + "  ".join(
            f"{name} {lag * 1000:.1f}ms"
            for name, lag in sorted(loops.items())))
    nodes = snap.get("nodes", [])
    if nodes:
        lines.append("")
        rows = []
        for n in nodes:
            cpu = n.get("resources", {}).get("CPU", 0)
            rows.append((
                n.get("node_id", "")[:12],
                "yes" if n.get("alive") else "NO",
                "-" if n.get("epoch") is None else str(n["epoch"]),
                "-" if n.get("phi") is None else f"{n['phi']:.2f}",
                "-" if n.get("last_heartbeat_age_s") is None
                else f"{n['last_heartbeat_age_s']:.1f}s",
                f"{cpu:g}",
                _fmt_bytes(n.get("rss_bytes")),
                f"{n.get('tasks_submitted_per_s', 0.0):.2f}",
                f"{n.get('tasks_finished_per_s', 0.0):.2f}",
            ))
        hdr = ("NODE", "ALIVE", "EPOCH", "PHI", "HB_AGE", "CPU",
               "RSS", "SUB/S", "FIN/S")
        widths = [max(len(hdr[i]), *(len(r[i]) for r in rows))
                  for i in range(len(hdr))]
        fmt = "  ".join(f"{{:<{w}}}" for w in widths)
        lines.append(fmt.format(*hdr))
        for r in rows:
            lines.append(fmt.format(*r))
    serve = snap.get("serve", {})
    if serve:
        lines.append("")
        rows = []
        for name in sorted(serve):
            d = serve[name]
            target = d.get("target_replicas")
            rows.append((
                name,
                str(d.get("replicas", 0)),
                "-" if target is None else str(target),
                f"{d.get('qps', 0.0):.2f}",
                f"{d.get('p50_s', 0.0) * 1000:.1f}ms",
                f"{d.get('p95_s', 0.0) * 1000:.1f}ms",
                f"{d.get('mean_queue_depth', 0.0):.1f}",
            ))
        hdr = ("DEPLOYMENT", "REPLICAS", "TARGET", "QPS", "P50", "P95",
               "QUEUE")
        widths = [max(len(hdr[i]), *(len(r[i]) for r in rows))
                  for i in range(len(hdr))]
        fmt = "  ".join(f"{{:<{w}}}" for w in widths)
        lines.append(fmt.format(*hdr))
        for r in rows:
            lines.append(fmt.format(*r))
    return "\n".join(lines)


def cmd_top(args) -> int:
    """`ray-tpu top [--once] [--interval S] [--window S] [--json]` —
    live cluster view rendered entirely from the head's windowed
    time-series store: per-node usage/epoch/suspicion + task rates,
    object-store bytes and spill rate, per-deployment qps/p95/queue,
    control-loop lag."""
    import time as _time

    _ensure_init()
    from ray_tpu._private.worker import global_worker
    rt = global_worker.runtime
    while True:
        snap = rt.top_snapshot(window=args.window)
        if args.json:
            print(json.dumps(snap, indent=2, default=str))
        else:
            if not args.once:
                print("\x1b[2J\x1b[H", end="")  # clear + home
            print(_render_top_frame(snap))
        if args.once:
            return 0
        try:
            _time.sleep(max(0.2, args.interval))
        except KeyboardInterrupt:
            return 0


def cmd_timeline(args) -> int:
    _ensure_init()
    from ray_tpu._private.state import timeline
    out = args.output or "timeline.json"
    events = timeline(out)
    print(f"Wrote {len(events)} events to {out}")
    return 0


def cmd_trace(args) -> int:
    """`ray-tpu trace [--id TRACE_ID | --tail N | --summary]
    [--perfetto out.json]` — inspect assembled distributed traces (the
    head merges spans shipped on metrics frames per trace_id; see
    /api/traces). Default lists recent traces; --id shows one trace's
    span tree + stage breakdown; --summary prints the cluster-level
    critical-path attribution; --perfetto writes Chrome-trace JSON with
    cross-process flow arrows for ui.perfetto.dev."""
    _ensure_init()
    from ray_tpu._private.worker import global_worker
    rt = global_worker.runtime

    def _fmt_s(sec):
        return f"{sec * 1000:.2f}ms" if sec < 1.0 else f"{sec:.3f}s"

    if args.perfetto:
        events = rt.trace_perfetto(args.id)
        if not events:
            print("no matching trace spans" if args.id
                  else "no trace spans assembled yet")
            return 1
        with open(args.perfetto, "w") as f:
            json.dump({"traceEvents": events}, f)
        print(f"Wrote {len(events)} events to {args.perfetto} "
              "(open in ui.perfetto.dev)")
        return 0
    if args.summary:
        summary = rt.trace_summary()
        print(f"traces assembled: {summary['traces']}")
        stages = summary["stages"]
        if not stages:
            return 0
        hdr = ("STAGE", "COUNT", "TOTAL", "SHARE", "P50", "P95")
        rows = [(stage, str(s["count"]), _fmt_s(s["total_s"]),
                 f"{s['share'] * 100:.1f}%", _fmt_s(s["p50_s"]),
                 _fmt_s(s["p95_s"]))
                for stage, s in sorted(stages.items(),
                                       key=lambda kv: -kv[1]["total_s"])]
        widths = [max(len(hdr[i]), *(len(r[i]) for r in rows))
                  for i in range(len(hdr))]
        fmt = "  ".join(f"{{:<{w}}}" for w in widths)
        print(fmt.format(*hdr))
        for r in rows:
            print(fmt.format(*r))
        return 0
    if args.id:
        trace = rt.trace_get(args.id)
        if trace is None:
            print(f"no trace {args.id!r}")
            return 1
        print(f"trace {trace['trace_id']}: {trace['span_count']} spans, "
              f"{_fmt_s(trace['duration_s'])} across "
              f"{len(trace['origins'])} origin(s)")
        for stage, s in sorted(trace["stages"].items(),
                               key=lambda kv: -kv[1]["total_s"]):
            print(f"  {stage:<14} x{s['count']:<4} "
                  f"{_fmt_s(s['total_s']):>10}  "
                  f"{s['share'] * 100:5.1f}%")
        # Indent each span under its parent (the cross-process chain).
        by_id = {s["span_id"]: s for s in trace["spans"]}
        t0 = trace["start_time"]

        def depth(span):
            d, seen = 0, set()
            while span.get("parent_id") in by_id:
                if span["span_id"] in seen:
                    break
                seen.add(span["span_id"])
                span = by_id[span["parent_id"]]
                d += 1
            return d
        for s in trace["spans"]:
            dur = s.get("duration") or 0.0
            origin = (f"{(s.get('node_id') or 'head')[:8]}/"
                      f"{s.get('component', '?')}-{s.get('pid', 0)}")
            print(f"  {'  ' * depth(s)}{s['name']} "
                  f"[+{_fmt_s(max(0.0, s['start_time'] - t0))} "
                  f"{_fmt_s(dur)}] @{origin}")
        return 0
    rows = rt.trace_list(args.tail)
    if not rows:
        print("no traces assembled yet (is tracing enabled and sampled?)")
        return 0
    for r in rows:
        print(f"{r['trace_id']}  {r['root']:<28} "
              f"{r['span_count']:>3} spans  "
              f"{_fmt_s(r['duration_s']):>10}  "
              f"origins={len(r['origins'])}")
    return 0


def cmd_list(args) -> int:
    _ensure_init()
    from ray_tpu.experimental.state import api
    fn = {
        "actors": api.list_actors,
        "tasks": api.list_tasks,
        "objects": api.list_objects,
        "nodes": api.list_nodes,
        "placement-groups": api.list_placement_groups,
    }[args.resource]
    print(json.dumps(fn(), indent=2, default=str))
    return 0


def cmd_actors(args) -> int:
    """`ray-tpu actors [--detached]` — list actors with lifetime;
    --detached shows only GCS-owned survivors (the ones an operator
    must `ray_tpu.kill()` explicitly post-mortem)."""
    _ensure_init()
    from ray_tpu.experimental.state import api
    filters = [("lifetime", "=", "detached")] if args.detached else None
    rows = api.list_actors(filters=filters)
    if args.json:
        print(json.dumps(rows, indent=2, default=str))
        return 0
    if not rows:
        print("no matching actors")
        return 0
    hdr = ("ACTOR_ID", "CLASS", "NAME", "NAMESPACE", "LIFETIME",
           "STATE", "RESTARTS")
    widths = [max(len(hdr[i]), *(len(str(r[k])) for r in rows))
              for i, k in enumerate(("actor_id", "class_name", "name",
                                     "namespace", "lifetime", "state",
                                     "num_restarts"))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    print(fmt.format(*hdr))
    for r in rows:
        print(fmt.format(r["actor_id"], r["class_name"], r["name"],
                         r["namespace"], r["lifetime"], r["state"],
                         r["num_restarts"]))
    return 0


def cmd_summary(args) -> int:
    _ensure_init()
    from ray_tpu.experimental.state import api
    fn = {"tasks": api.summarize_tasks,
          "objects": api.summarize_objects}[args.resource]
    print(json.dumps(fn(), indent=2, default=str))
    return 0


def cmd_metrics(args) -> int:
    if getattr(args, "grafana", False):
        from ray_tpu.dashboard.grafana import generate_dashboard
        print(json.dumps(generate_dashboard(), indent=2))
        return 0
    _ensure_init()
    from ray_tpu._private.worker import global_worker
    runtime = getattr(global_worker, "_runtime", None)
    text_fn = getattr(runtime, "cluster_metrics_text", None)
    if text_fn is not None:
        # Cluster-wide exposition: every node/worker's series with
        # node_id/pid/component labels (what /metrics serves).
        print(text_fn())
    else:
        from ray_tpu.util.metrics import export_prometheus
        print(export_prometheus())
    return 0


def cmd_devices(args) -> int:
    import jax
    for d in jax.devices():
        print(f"{d.id}: {d.device_kind} (process {d.process_index}, "
              f"platform {d.platform})")
    return 0


def cmd_job(args) -> int:
    """`ray-tpu job submit/status/logs/stop/list` (analog of the reference's
    `ray job` CLI, dashboard/modules/job/cli.py)."""
    from ray_tpu.job_submission import JobSubmissionClient
    client = JobSubmissionClient(getattr(args, "address", None))
    if args.job_command == "submit":
        runtime_env = None
        if args.working_dir:
            runtime_env = {"working_dir": args.working_dir}
        import shlex
        entrypoint = list(args.entrypoint)
        if entrypoint and entrypoint[0] == "--":
            entrypoint = entrypoint[1:]
        job_id = client.submit_job(
            entrypoint=" ".join(shlex.quote(t) for t in entrypoint),
            runtime_env=runtime_env,
            submission_id=args.submission_id)
        print(job_id)
        if args.wait:
            for chunk in client.tail_job_logs(job_id, timeout=args.timeout):
                sys.stdout.write(chunk)
            status = client.get_job_status(job_id)
            print(f"Job {job_id} finished: {status.value}")
            return 0 if status.value == "SUCCEEDED" else 1
        return 0
    if args.job_command == "status":
        print(client.get_job_status(args.job_id).value)
        return 0
    if args.job_command == "logs":
        print(client.get_job_logs(args.job_id), end="")
        return 0
    if args.job_command == "stop":
        stopped = client.stop_job(args.job_id)
        print("stopped" if stopped else "already terminal")
        return 0
    if args.job_command == "list":
        for j in client.list_jobs():
            print(f"{j.submission_id}\t{j.status.value}\t{j.entrypoint}")
        return 0
    return 1


def cmd_logs(args) -> int:
    """`ray-tpu logs [filename] [--node/--pid/--tail/--follow]` —
    read the session's captured per-process logs from disk (reference:
    `ray logs`, scripts/scripts.py:2390). Deliberately does NOT
    initialize a runtime: it reads the CURRENT session when run inside
    a driver, else the newest ``session_latest`` on disk."""
    import time

    from ray_tpu.experimental.state import api
    kwargs = dict(filename=args.filename, node_id=args.node,
                  pid=args.pid)
    try:
        if args.list:
            for row in api.list_logs(node_id=args.node):
                print(f"{row['node']}\t{row['size_bytes']}\t"
                      f"{row['filename']}")
            return 0
        lines = api.get_log(tail=args.tail, **kwargs)
    except FileNotFoundError as exc:
        print(str(exc), file=sys.stderr)
        return 1
    for line in lines:
        print(line)
    if not args.follow:
        return 0
    seen = len(api.get_log(tail=-1, **kwargs))
    try:
        while True:
            time.sleep(1.0)
            all_lines = api.get_log(tail=-1, **kwargs)
            for line in all_lines[seen:]:
                print(line)
            seen = max(seen, len(all_lines))
    except KeyboardInterrupt:
        return 0


def cmd_alerts(args) -> int:
    """`ray-tpu alerts [--history] [--json]` — active alert instances
    (firing → pending → resolved) and the rule table from the head's
    alert engine; every number comes from the time-series store."""
    _ensure_init()
    from ray_tpu._private.worker import global_worker
    snap = global_worker.runtime.alerts_snapshot()
    if args.json:
        print(json.dumps(snap, indent=2, default=str))
        return 0
    print(f"alerting {'enabled' if snap.get('enabled') else 'DISABLED'} — "
          f"eval period {snap.get('period_s', 0):g}s — "
          f"{len(snap.get('rules', []))} rule(s) — "
          f"{len(snap.get('firing', []))} firing")
    alerts = snap.get("alerts", [])
    if alerts:
        rows = [(a.get("state", "").upper(), a.get("rule", ""),
                 a.get("key") or "-", a.get("severity", ""),
                 f"{a.get('value', 0):.4g}", f"{a.get('since_s', 0):.0f}s")
                for a in alerts]
        hdr = ("STATE", "RULE", "KEY", "SEVERITY", "VALUE", "SINCE")
        widths = [max(len(hdr[i]), *(len(r[i]) for r in rows))
                  for i in range(len(hdr))]
        fmt = "  ".join(f"{{:<{w}}}" for w in widths)
        print(fmt.format(*hdr))
        for r in rows:
            print(fmt.format(*r))
    else:
        print("no active alert instances")
    if args.history:
        for h in snap.get("history", []):
            print(f"{h.get('since_s', 0):>8.1f}s ago  "
                  f"{h.get('state', ''):<9} {h.get('rule', '')}"
                  f"[{h.get('key') or '-'}] value={h.get('value', 0):.4g}")
    return 0


def cmd_xfer(args) -> int:
    """`ray-tpu xfer [--links|--objects|--tree] [--window S] [--json]`
    — the dataplane flow plane: per-link transfer matrix (windowed
    MB/s, p95 latency, failovers/errors per src->dst node pair), the
    per-object pull fan-out table (broadcast amplification), and the
    last broadcast's spanning tree with per-edge MB/s."""
    _ensure_init()
    from ray_tpu._private.worker import global_worker
    snap = global_worker.runtime.flows_snapshot(window=args.window)
    if args.json:
        print(json.dumps(snap, indent=2, default=str))
        return 0
    if args.tree:
        bc = snap.get("broadcast")
        if not bc:
            print("no broadcast recorded")
            return 0
        print(f"last broadcast — key {bc.get('key', '?')[:32]}, "
              f"{_fmt_bytes(bc.get('size'))} to {bc.get('nodes', 0)} "
              f"node(s), fanout {bc.get('fanout', '?')}, depth "
              f"{bc.get('depth', 0)}, {bc.get('age_s', 0.0):.0f}s ago")
        children: dict = {}
        for e in bc.get("edges", []):
            children.setdefault(e.get("src", "?"), []).append(e)

        def _edge_line(e) -> str:
            secs = e.get("secs")
            rate = (f"{e.get('bytes', 0) / secs / 1e6:.1f} MB/s"
                    if secs else "-")
            line = f"{e.get('dst', '?')[:12]}  " \
                   f"[{'ok' if e.get('ok') else 'FAILED'}, {rate}"
            if e.get("failovers"):
                line += f", {e['failovers']} failover(s)"
            return line + "]"

        def _walk(src: str, prefix: str) -> None:
            kids = children.get(src, [])
            for i, e in enumerate(kids):
                last = i == len(kids) - 1
                print(prefix + ("`-- " if last else "|-- ")
                      + _edge_line(e))
                _walk(e.get("dst", ""),
                      prefix + ("    " if last else "|   "))

        root = bc.get("root", "head")
        print(root if root == "head" else root[:12])
        _walk(root, "")
        return 0
    stats = snap.get("stats", {})
    print(f"transfer ledger — window {snap.get('window_s', 0):g}s — "
          f"{stats.get('links', 0)} link(s), "
          f"{stats.get('objects', 0)} object(s), "
          f"{stats.get('records', 0)} record(s) merged")
    show_links = not args.objects
    show_objects = not args.links
    links = snap.get("links", [])
    if show_links:
        if links:
            rows = [(lk.get("src", "")[:12] or "-",
                     lk.get("dst", "")[:12] or "-",
                     f"{lk.get('mbps', 0.0):.2f}",
                     _fmt_bytes(lk.get("window_bytes")),
                     _fmt_bytes(lk.get("bytes_total")),
                     str(lk.get("records", 0)),
                     f"{lk.get('p95_s', 0.0) * 1000:.1f}ms",
                     str(lk.get("failovers", 0)),
                     str(lk.get("errors", 0)))
                    for lk in links]
            hdr = ("SRC", "DST", "MB/S", "WINDOW", "TOTAL", "PULLS",
                   "P95", "FAILOVER", "ERR")
            widths = [max(len(hdr[i]), *(len(r[i]) for r in rows))
                      for i in range(len(hdr))]
            fmt = "  ".join(f"{{:<{w}}}" for w in widths)
            print(fmt.format(*hdr))
            for r in rows:
                print(fmt.format(*r))
        else:
            print("no transfer links recorded")
    objects = snap.get("objects", [])
    if show_objects:
        if show_links:
            print()
        if objects:
            rows = [(o.get("key", "")[:24],
                     str(o.get("fanout", 0)),
                     str(len(o.get("nodes", []))),
                     _fmt_bytes(o.get("bytes_total")),
                     str(o.get("pulls", 0)))
                    for o in objects]
            hdr = ("OBJECT", "FANOUT", "NODES", "BYTES", "PULLS")
            widths = [max(len(hdr[i]), *(len(r[i]) for r in rows))
                      for i in range(len(hdr))]
            fmt = "  ".join(f"{{:<{w}}}" for w in widths)
            print(fmt.format(*hdr))
            for r in rows:
                print(fmt.format(*r))
        else:
            print("no object fan-out recorded")
    return 0


def cmd_events(args) -> int:
    """`ray-tpu events [--severity S] [--source S] [--node N]
    [--limit N] [--follow] [--json]` — the head's cluster event
    journal (membership, serve, train, spill, alert transitions)."""
    import time as _time

    _ensure_init()
    from ray_tpu._private.worker import global_worker
    rt = global_worker.runtime

    def _print(rows) -> None:
        for ev in rows:
            if args.json:
                print(json.dumps(ev, default=str))
                continue
            labels = ev.get("labels") or {}
            extra = " ".join(f"{k}={v}"
                             for k, v in sorted(labels.items()))
            node = (ev.get("node_id") or "")[:12] or "-"
            print(f"{ev.get('seq', 0):>6}  {ev.get('age_s', 0):>7.1f}s  "
                  f"{ev.get('severity', ''):<8} "
                  f"{ev.get('source', ''):<14} {node:<12}  "
                  f"{ev.get('message', '')}"
                  + (f"  [{extra}]" if extra else ""))

    try:
        rows = rt.cluster_events(severity=args.severity,
                                 source=args.source, node_id=args.node,
                                 limit=args.limit)
    except ValueError as exc:
        print(str(exc), file=sys.stderr)
        return 1
    _print(rows)
    if not args.follow:
        return 0
    last_seq = rows[-1]["seq"] if rows else 0
    try:
        while True:
            _time.sleep(1.0)
            fresh = rt.cluster_events(severity=args.severity,
                                      source=args.source,
                                      node_id=args.node,
                                      since_seq=last_seq)
            _print(fresh)
            if fresh:
                last_seq = fresh[-1]["seq"]
    except KeyboardInterrupt:
        return 0


def cmd_profile(args) -> int:
    """CPU profiles, four ways: this driver process (default), a node
    daemon (--node), any cluster worker by pid (--pid, cooperative —
    resolved through the owning daemon, no py-spy needed), or the whole
    cluster at once (--cluster, synchronized burst fanned to every live
    daemon + the head, merged). --report instead prints the loop-lag
    flight recorder's incidents. Writes a speedscope JSON (open at
    speedscope.app) or collapsed flamegraph stacks."""
    _ensure_init()
    import json as _json

    from ray_tpu._private.profiling import profile_self
    from ray_tpu._private.worker import global_worker
    runtime = global_worker.runtime
    if args.report:
        incidents = runtime.profile_incidents()
        if not incidents:
            print("no loop-lag incidents recorded")
            return 0
        for inc in incidents:
            print(f"loop={inc['loop']} lag={inc['lag_s']:.3f}s "
                  f"(threshold {inc['threshold_s']:.3f}s) "
                  f"component={inc['component'] or '?'} "
                  f"node={inc['node_id'][:8] or 'head'} "
                  f"pid={inc['pid']} scope={inc['scope']} "
                  f"{inc['age_s']:.0f}s ago")
            for stack, weight in inc["top_stacks"][:10]:
                print(f"  {weight:>8}  {stack}")
        return 0
    fmt = "speedscope" if args.output.endswith(".json") else "folded"
    if args.cluster:
        result = runtime.profile_cluster(args.duration, args.hz, fmt)
    elif args.pid is not None:
        try:
            result = runtime.profile_pid(args.pid, args.duration,
                                         args.hz, fmt)
        except ValueError as exc:
            print(exc)
            return 1
    elif args.node:
        conn = None
        for nid, c in runtime._remote_nodes.items():
            if nid.hex().startswith(args.node):
                conn = c
                break
        if conn is None:
            print(f"no live node matches {args.node!r}")
            return 1
        result = conn.profile(args.duration, args.hz, fmt)
    else:
        result = profile_self(args.duration, args.hz, fmt)
    with open(args.output, "w") as f:
        if fmt == "speedscope":
            _json.dump(result, f)
        else:
            f.write(result)
    print(f"Wrote {fmt} profile to {args.output}")
    return 0


def cmd_grafana(args) -> int:
    from ray_tpu.dashboard.grafana import write_dashboards
    for path in write_dashboards(args.out):
        print(f"Wrote {path}")
    return 0


def cmd_microbenchmark(args) -> int:
    """`ray-tpu microbenchmark` — the core ops/s suite (reference:
    release/microbenchmark/run_microbenchmark.py)."""
    from ray_tpu._private.ray_perf import main as perf_main
    perf_main(duration=args.duration)
    return 0


def cmd_start(args) -> int:
    """`ray-tpu start` — join (or head) a multi-process cluster
    (reference: `ray start --head/--address`, scripts/scripts.py:529)."""
    import json
    import time

    if args.head:
        import ray_tpu
        if not ray_tpu.is_initialized():
            ray_tpu.init(num_cpus=args.num_cpus, num_tpus=args.num_tpus,
                         _memory=args.memory,
                         resources=(json.loads(args.resources)
                                    if args.resources else None))
        host, port = ray_tpu.start_head_server(port=args.port,
                                               host=args.host)
        print(f"Head node listening for node daemons on {host}:{port}")
        print(f"Join with: ray-tpu start --address <this-host>:{port}")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            ray_tpu.shutdown()
        return 0
    if not args.address:
        print("start requires --head or --address host:port",
              file=sys.stderr)
        return 1
    from ray_tpu._private.multinode import run_node
    run_node(args.address, num_cpus=args.num_cpus,
             num_tpus=args.num_tpus, memory=args.memory,
             resources=json.loads(args.resources) if args.resources
             else None,
             labels=json.loads(args.labels) if args.labels else None)
    return 0


def cmd_up(args) -> int:
    """`ray-tpu up cluster.yaml` (reference: `ray up`,
    scripts/scripts.py:1216)."""
    from ray_tpu.autoscaler.launcher import up
    out = up(args.config_file, no_head=args.no_head)
    print(f"cluster {out['cluster_name']}: created "
          f"{out['created']['head']} head, "
          f"{out['created']['workers']} workers; nodes now: "
          f"{out['nodes']}")
    return 0


def cmd_down(args) -> int:
    """`ray-tpu down cluster.yaml` (reference: `ray down`)."""
    from ray_tpu.autoscaler.launcher import down
    nodes = down(args.config_file)
    print(f"terminated {len(nodes)} nodes: {nodes}")
    return 0


def cmd_dashboard(args) -> int:
    """`ray-tpu dashboard` — run the HTTP observability endpoint."""
    import time

    import ray_tpu
    from ray_tpu.dashboard import start_dashboard
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    head = start_dashboard(args.host, args.port)
    print(f"Dashboard listening on http://{args.host}:{head.bound_port}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        head.stop()
    return 0


def cmd_serve(args) -> int:
    """`ray-tpu serve deploy/status/shutdown` (analog of the reference's
    `serve` CLI, serve/scripts.py)."""
    import json

    from ray_tpu import serve
    if args.serve_command == "deploy":
        from ray_tpu.serve.schema import apply_config
        with open(args.config_file) as f:
            config = json.load(f)
        apply_config(config)
        print("deployed")
        return 0
    if args.serve_command == "status":
        print(json.dumps(serve.status(), indent=2))
        return 0
    if args.serve_command == "shutdown":
        serve.shutdown()
        print("shut down")
        return 0
    return 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="ray-tpu",
        description="TPU-native distributed computing framework CLI")
    sub = parser.add_subparsers(dest="command", required=True)

    sub.add_parser("status", help="cluster resource + task summary")
    p = sub.add_parser("top", help="live cluster view from the head's "
                                   "windowed time-series store")
    p.add_argument("--once", action="store_true",
                   help="print one frame and exit")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh period in seconds (default 2)")
    p.add_argument("--window", type=float, default=None,
                   help="derivation window in seconds (default 30)")
    p.add_argument("--json", action="store_true",
                   help="emit the raw snapshot as JSON")
    sub.add_parser("memory", help="object store summary")
    p = sub.add_parser("timeline", help="dump chrome://tracing JSON")
    p.add_argument("-o", "--output", default=None)
    p = sub.add_parser("trace", help="inspect assembled distributed "
                                     "traces (cross-process spans)")
    p.add_argument("--id", default=None,
                   help="show one trace's span tree + stage breakdown")
    p.add_argument("--tail", type=int, default=20,
                   help="list the N most recent traces (default 20)")
    p.add_argument("--summary", action="store_true",
                   help="cluster-level per-stage critical-path breakdown")
    p.add_argument("--perfetto", default=None, metavar="OUT_JSON",
                   help="write Chrome-trace JSON (slices + flow arrows); "
                        "combine with --id for a single trace")
    p = sub.add_parser("list", help="list cluster state")
    p.add_argument("resource", choices=["actors", "tasks", "objects",
                                        "nodes", "placement-groups"])
    p = sub.add_parser("actors", help="list actors (lifetime-aware)")
    p.add_argument("--detached", action="store_true",
                   help="only GCS-owned detached actors")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p = sub.add_parser("summary", help="summarize cluster state")
    p.add_argument("resource", choices=["tasks", "objects"])
    p = sub.add_parser("metrics",
                       help="print cluster-wide Prometheus metrics")
    p.add_argument("--grafana", action="store_true",
                   help="print the generated Grafana dashboard JSON "
                        "instead of the exposition")
    sub.add_parser("devices", help="list visible accelerator devices")

    p = sub.add_parser("job", help="submit and manage jobs")
    jsub = p.add_subparsers(dest="job_command", required=True)
    ps = jsub.add_parser("submit", help="run an entrypoint as a job")
    ps.add_argument("--submission-id", default=None)
    ps.add_argument("--working-dir", default=None)
    ps.add_argument("--wait", action="store_true",
                    help="stream logs until the job finishes")
    ps.add_argument("--timeout", type=float, default=3600.0)
    ps.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for name in ("status", "logs", "stop"):
        pj = jsub.add_parser(name)
        pj.add_argument("job_id")
    jsub.add_parser("list")

    p = sub.add_parser("logs", help="read captured session logs "
                                    "(worker/daemon stdout+stderr)")
    p.add_argument("filename", nargs="?", default=None,
                   help="exact log filename (default: all capture "
                        "files)")
    p.add_argument("--node", default=None,
                   help="node id prefix (or 'head') to read")
    p.add_argument("--pid", type=int, default=None,
                   help="only files of this process id")
    p.add_argument("--tail", type=int, default=1000,
                   help="last N lines (-1 for everything)")
    p.add_argument("--follow", "-f", action="store_true",
                   help="keep polling for new lines")
    p.add_argument("--list", action="store_true",
                   help="list the session's log files instead")

    p = sub.add_parser("alerts", help="active alerts + rule table from "
                                      "the head's alert engine")
    p.add_argument("--history", action="store_true",
                   help="also print the bounded transition history")
    p.add_argument("--json", action="store_true",
                   help="emit the raw snapshot as JSON")
    p = sub.add_parser("xfer", help="dataplane flow plane: per-link "
                                    "transfer matrix + object fan-out")
    p.add_argument("--links", action="store_true",
                   help="only the per-link MB/s matrix")
    p.add_argument("--objects", action="store_true",
                   help="only the per-object fan-out table")
    p.add_argument("--tree", action="store_true",
                   help="render the last broadcast's spanning tree "
                        "with per-edge MB/s")
    p.add_argument("--window", type=float, default=None,
                   help="MB/s window in seconds (clamped to the store's)")
    p.add_argument("--json", action="store_true",
                   help="emit the raw snapshot as JSON")
    p = sub.add_parser("events", help="cluster event journal "
                                      "(membership, serve, train, "
                                      "spill, alert transitions)")
    p.add_argument("--severity", default=None,
                   help="minimum severity (info/warning/error/critical)")
    p.add_argument("--source", default=None,
                   help="only events from this subsystem")
    p.add_argument("--node", default=None,
                   help="only events stamped with this node id")
    p.add_argument("--limit", type=int, default=None,
                   help="last N matching events")
    p.add_argument("--follow", "-f", action="store_true",
                   help="keep polling for new events (by seq)")
    p.add_argument("--json", action="store_true",
                   help="one JSON object per line")

    p = sub.add_parser("profile", help="sample CPU stacks on demand "
                                       "(driver, --node <id>, --pid, "
                                       "--cluster) or --report the "
                                       "loop-lag flight recorder")
    p.add_argument("--node", default=None,
                   help="node id prefix to profile (default: this "
                        "process)")
    p.add_argument("--pid", type=int, default=None,
                   help="profile a cluster worker by pid, resolved "
                        "through its owning daemon (no py-spy needed)")
    p.add_argument("--cluster", action="store_true",
                   help="synchronized burst: every live daemon + the "
                        "head sample together, merged into one graph")
    p.add_argument("--report", action="store_true",
                   help="print the loop-lag flight recorder's "
                        "incidents instead of sampling")
    p.add_argument("--duration", type=float, default=5.0)
    p.add_argument("--hz", type=int, default=100)
    p.add_argument("--output", default="profile.speedscope.json",
                   help=".json -> speedscope, anything else -> "
                        "collapsed stacks")
    p = sub.add_parser("grafana-dashboards",
                       help="generate Grafana dashboard JSON for the "
                            "cluster's Prometheus metrics")
    p.add_argument("--out", default="grafana_dashboards")
    p = sub.add_parser("microbenchmark",
                       help="core ops/s suite (tasks, actors, put/get)")
    p.add_argument("--duration", type=float, default=2.0)

    p = sub.add_parser("start", help="start a head or join as a node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--host", default="0.0.0.0",
                   help="head bind address (the control plane is "
                        "unauthenticated: expose only on trusted networks)")
    p.add_argument("--address", default=None,
                   help="head host:port to join as a node daemon")
    p.add_argument("--port", type=int, default=6380)
    p.add_argument("--num-cpus", type=float, default=1.0)
    p.add_argument("--num-tpus", type=float, default=None,
                   help="TPU chips this node offers (default: the chips "
                        "the host exposes to this process)")
    p.add_argument("--memory", type=float, default=float(1 << 30))
    p.add_argument("--resources", default=None,
                   help="extra resources as JSON")
    p.add_argument("--labels", default=None,
                   help="node labels as JSON (cloud providers tag their "
                        "nodes here, e.g. provider_node_id)")

    p = sub.add_parser("up", help="create a cluster from a YAML config")
    p.add_argument("config_file")
    p.add_argument("--no-head", action="store_true",
                   help="only create workers (head runs elsewhere)")

    p = sub.add_parser("down", help="terminate a cluster's nodes")
    p.add_argument("config_file")

    p = sub.add_parser("dashboard", help="run the HTTP dashboard")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8265)

    p = sub.add_parser("serve", help="deploy and inspect Serve apps")
    ssub = p.add_subparsers(dest="serve_command", required=True)
    pd = ssub.add_parser("deploy", help="deploy from a JSON config file")
    pd.add_argument("config_file")
    ssub.add_parser("status")
    ssub.add_parser("shutdown")

    args = parser.parse_args(argv)
    handler = {
        "status": cmd_status,
        "top": cmd_top,
        "memory": cmd_memory,
        "timeline": cmd_timeline,
        "trace": cmd_trace,
        "list": cmd_list,
        "actors": cmd_actors,
        "summary": cmd_summary,
        "metrics": cmd_metrics,
        "devices": cmd_devices,
        "job": cmd_job,
        "logs": cmd_logs,
        "serve": cmd_serve,
        "dashboard": cmd_dashboard,
        "start": cmd_start,
        "up": cmd_up,
        "down": cmd_down,
        "microbenchmark": cmd_microbenchmark,
        "profile": cmd_profile,
        "grafana-dashboards": cmd_grafana,
        "alerts": cmd_alerts,
        "xfer": cmd_xfer,
        "events": cmd_events,
    }[args.command]
    return handler(args)


if __name__ == "__main__":
    sys.exit(main())
