"""Open-loop client sessions against the query service (``QueryService``).

A session is one client: it registers on arrival with a path query (an
algorithm and a source), submits its windows at once (a seeded start,
sliding to the last snapshot), and unregisters once all are answered.
One query is one window. Arrivals follow the benchmark's own clock at a
fixed rate of sessions per second; the driver is one thread: it submits
what is due, runs ``turn()`` while there is a backlog, and otherwise
sleeps until the next arrival. Latency runs from when a query was due to
when ``turn()`` handed its answer back. After the window the backlog is
drained for up to another ``--seconds``; what is still unanswered then
has failed.

Every seed gets the same sessions in another order: the inter-arrival
gaps are the ``M`` mid-quantiles of the exponential distribution of the
rate, and each session attribute (algorithm, the pair of window width
and start, campaign width, source rank under Zipf popularity) is a
stratified sample, each shuffled by the seed, so every seed offers the
same number of window queries. The source pool is ``source_pool`` of
the deployment's search keys, in the same popularity order for every
seed (``graph500.History.search_keys``).

Traffic parameters: ``sessions_per_s``, ``algs``, ``source_pool``,
``zipf_s``, ``width_range``, ``campaign_widths``.
"""

from __future__ import annotations

import time

import numpy as np

import graph500


def _stratified(values, m: int, rng) -> list:
    """``m`` draws, each of ``values`` equally often, in a seeded order."""
    return [values[i] for i in rng.permutation(np.arange(m) % len(values))]


def plan_sessions(traffic: dict, snapshots: int, pool, seconds: float,
                  rng) -> "list[dict]":
    """The window's sessions, in arrival order (``due`` in window seconds)."""
    rate = traffic["sessions_per_s"]
    m = max(1, round(rate * seconds))
    quantiles = (np.arange(m) + 0.5) / m
    gaps = rng.permutation(-np.log1p(-quantiles) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    algs = _stratified(traffic["algs"], m, rng)
    lo_w, hi_w = traffic["width_range"]
    # A session slides from its start to the last snapshot: at least 3 windows.
    shapes = _stratified([(w, a) for w in range(lo_w, hi_w + 1)
                          for a in range(snapshots - w - 1)], m, rng)
    campaigns = _stratified(traffic["campaign_widths"], m, rng)
    pop = 1.0 / np.arange(1, len(pool) + 1) ** traffic["zipf_s"]
    ranks = np.searchsorted(np.cumsum(pop) / pop.sum(), rng.permutation(quantiles))
    sessions = []
    for i in range(m):
        w, start = shapes[i]
        sessions.append({
            "due": float(due[i]), "alg": algs[i],
            "source": int(pool[min(ranks[i], len(pool) - 1)]),
            "campaign": int(campaigns[i]),
            "windows": [(a, a + w - 1) for a in range(start, snapshots - w + 1)]})
    return sessions


def _bucket(edges: int, granule: int) -> int:
    # The program's block padding: pow2 multiples of its granule.
    b = granule
    while b < edges:
        b *= 2
    return b


def warm_up(run, service, isolated: "list[int]") -> int:
    """Run every launch shape the window can use; returns the launches made.

    A packed launch's programs follow its lane bucket, the stacked
    slide-Δ width and the anchor view it shares: the anchor's common graph
    when the anchor state was cached or rebuilt, or a covering anchor's
    common graph plus the hop Δ when it was reached by an anchor hop. The
    host-side gathers follow the number of distinct queries packed into
    one launch. Each shape gets a launch of its own from a fresh isolated
    source, so it compiles and loads its programs while its fixpoint ends
    at once.
    """
    from repro.graph.semiring import ALL_SEMIRINGS

    store, traffic = run.store, run.traffic
    last = store.seq.num_snapshots - 1
    lo_w, hi_w = traffic["width_range"]
    windows = sorted({(a, a + w - 1) for w in range(lo_w, hi_w + 1)
                      for a in range(0, last - w + 2)})
    los = sorted({a for a, _ in windows})
    size = store.window_size
    shape = lambda e: _bucket(e, store.granule)            # noqa: E731
    lane_buckets = [1 << i for i in range(service.lane_budget.bit_length())]
    if lane_buckets[-1] < service.lane_budget:
        lane_buckets.append(2 * lane_buckets[-1])
    targets = {}   # (lanes, Δ width, shared view shapes) -> (cover, anchor lo, window)
    for w in windows:
        for lo in (x for x in los if x <= w[0]):
            for c in [None] + [x for x in los if x < lo]:
                shared = ((shape(size(lo, last)),) if c is None else
                          (shape(size(c, last)),
                           shape(size(lo, last) - size(c, last))))
                wide = shape(size(*w) - size(lo, last))
                for lanes in lane_buckets:
                    if lanes > 1 or lo == w[0]:
                        targets.setdefault((lanes, wide, shared), (c, lo, w))
    sources = iter(isolated)
    launches = 0

    def serve(requests) -> None:
        """One turn for ``[(semiring, source, campaign)]``; then unregister."""
        nonlocal launches
        clients = []
        for sr, src, campaign in requests:
            c = service.register(sr, src, campaign_width=len(campaign),
                                 name=f"warm-{launches}-{len(clients)}")
            service.submit(c, campaign)
            clients.append(c)
        while service.pending():
            launches += len(service.turn())
        for c in clients:
            service.unregister(c)

    for alg in traffic["algs"]:
        sr = ALL_SEMIRINGS[alg]
        for (lanes, _, _), (c, lo, w) in sorted(targets.items(), key=str):
            src = next(sources)
            if c is not None:      # cache the cover, so the anchor hops from it
                serve([(sr, src, [(c, last)])])
            serve([(sr, src, [w] if lanes == 1
                    else [(lo, w[1])] + [w] * (lanes - 1))])
    # Distinct queries per launch (k of n lanes): identical windows, so the
    # campaigns pack into one launch; one algorithm suffices (host-side shapes).
    sr = ALL_SEMIRINGS[traffic["algs"][0]]
    w = windows[-1]
    for n in range(1, service.lane_budget + 1):
        for k in range(1, n + 1):
            lanes = [n // k + (i < n % k) for i in range(k)]
            serve([(sr, isolated[i], [w] * lanes[i]) for i in range(k)])
    return launches


def setup(run) -> None:
    from repro.core.service import QueryService

    cfg = run.config["service"]
    service = QueryService(run.store, lane_budget=cfg["lane_budget"])
    pool = run.history.search_keys(run.traffic["source_pool"])
    isolated = graph500.isolated_vertices(run.history.snapshots,
                                          run.history.num_nodes, 512,
                                          run.rng(4))
    t0 = time.perf_counter()
    launches = warm_up(run, service, isolated)
    run.log(f"warm-up: {launches} launches in {time.perf_counter() - t0:.3f}s")
    sessions = plan_sessions(run.traffic, run.store.seq.num_snapshots, pool,
                             run.seconds, run.rng(1))
    run.state = {"service": service, "sessions": sessions}


def _percentile(xs, q: float) -> float:
    """Nearest-rank percentile ``q`` in [0, 100]."""
    xs = sorted(xs)
    return xs[max(1, int(np.ceil(q / 100 * len(xs)))) - 1]


def window(run) -> None:
    from repro.graph.semiring import ALL_SEMIRINGS

    service, sessions = run.state["service"], run.state["sessions"]
    live = []             # [client, session, answers recorded]
    late, done = [], {}   # generator lateness; (session, window) -> finish
    first_launch = len(service.launch_log)

    def admit(now: float, upto: float) -> int:
        i = len(late)
        while i < len(sessions) and sessions[i]["due"] <= upto:
            s = sessions[i]
            c = service.register(ALL_SEMIRINGS[s["alg"]], s["source"],
                                 campaign_width=s["campaign"], name=f"s{i}")
            service.submit(c, s["windows"])
            live.append([c, i, 0])
            late.append(now - s["due"])
            i += 1
        return i

    def harvest(now: float) -> None:
        for entry in list(live):
            c, i, seen = entry
            got = list(c.results)
            for wnd in got[seen:]:
                done[(i, wnd)] = now
                run.answer(wnd, sessions[i]["alg"], sessions[i]["source"],
                           c.results[wnd])
            entry[2] = len(got)
            if len(got) == len(sessions[i]["windows"]):
                service.unregister(c)
                live.remove(entry)

    t_open = run.open_window()
    clock = lambda: time.perf_counter() - t_open          # noqa: E731
    while (now := clock()) < run.seconds:
        nxt = admit(now, now)
        if service.pending():
            with run.span("cb.turn"):
                service.turn()
            harvest(clock())
        else:
            until = sessions[nxt]["due"] if nxt < len(sessions) else run.seconds
            with run.span("cb.wait"):
                time.sleep(max(0.0, min(until, run.seconds) - clock()))
    window_s = run.close_window()
    last_launch = len(service.launch_log)
    admitted = admit(clock(), run.seconds - 1e-9)
    while service.pending() and clock() < window_s + run.seconds:
        service.turn()
        harvest(clock())
    drained_at = clock()

    due = {(i, w): sessions[i]["due"] for i in range(admitted)
           for w in sessions[i]["windows"]}
    latency = [done.get(q, drained_at) - d for q, d in due.items()]
    answered_in_window = sum(1 for t in done.values() if t <= window_s)
    run.attempted = len(due)
    run.failed = run.unanswered = len(due) - len(done)
    run.e2e["query_p95_s"] = _percentile(latency, 95)
    run.e2e["queries_per_s"] = answered_in_window / window_s
    lanes = [r.lanes for r in service.launch_log[first_launch:last_launch]]

    def backlog(t: float) -> int:
        return (sum(1 for d in due.values() if d <= t)
                - sum(1 for f in done.values() if f <= t))

    run.counters.update(answered=answered_in_window, launch_lanes=lanes,
                        backlog=[backlog(window_s * k / 4)
                                 for k in (1, 2, 3, 4)])
    run.log(f"sessions: {admitted} admitted, {len(due)} queries, "
            f"{answered_in_window} answered in the window, {len(done)} after "
            f"the drain ({drained_at - window_s:.3f}s); generator late p95 "
            f"{_percentile(late, 95):.6f}s max {max(late):.6f}s")
    run.log(f"latency s: p50 {_percentile(latency, 50):.6f} p95 "
            f"{_percentile(latency, 95):.6f} p99 {_percentile(latency, 99):.6f} "
            f"max {max(latency):.6f}; launches in the window {len(lanes)}, "
            f"lanes per launch {np.mean(lanes) if lanes else 0:.3f}; backlog "
            f"at each quarter of the window {run.counters['backlog']}")
