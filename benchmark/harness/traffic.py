"""The one general traffic generator. A mix is a data file,
``benchmark/traffic/<mix>.json``; a new mix needs no code.

Keys of a mix:

``loop``      ``closed`` (each client sends its next query when the last
              answer is in) or ``open`` (queries are due on a schedule,
              whatever the system does; latency counts from the due time)
``clients``   closed: number of streams, each a thread with its own
              client. open: size of the sender pool
``classes``   ``all`` (every query class of the configuration) or a list
``order``     ``permutation``: a stream's work is cycles, each a fresh
              seeded permutation of the classes (TPC-H cl. 5.3.5 seeds
              the order per stream); ``weighted``: each query drawn from
              ``weights`` (class -> weight)
``think_ms``  closed: pause of a stream between answer and next query
``rate_per_s``, ``arrivals``  open: mean rate, and ``fixed`` or
              ``poisson`` gaps
``warm_cycles``  cycles each stream runs before the window (default 1)

Every seed gives the same classes in the same numbers in another order:
a closed stream runs WHOLE cycles and stops at the first cycle boundary
past the deadline, so the window's work is cycles of the same content.

Where the configuration declares substitution parameters, ``parameters``
is its module (``reference/<module>.py``): each query carries a set
drawn by ``parameters.draw(cls, rng)`` from an rng of its own stream's
(``params/<stream>``), so the rng that orders the classes draws exactly
what it draws without them, and every seed keeps its class order.
"""

import contextlib
import json
import os
import random
import threading
import time
from typing import Callable, List

from .stats import Record

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"mix {name}: loop must be closed or open")
    return mix


def load_sql(cls: str, config: dict) -> str:
    """The SQL of a query class of ``config``: ``traffic/queries/`` holds
    it, in the sub-directory the configuration names under
    ``queries_dir`` (so two benchmarks may each have a ``q3``), else
    flat."""
    with open(os.path.join(HERE, "traffic", "queries",
                           config.get("queries_dir", ""),
                           f"{cls}.sql")) as f:
        return f.read()


def classes_of(mix: dict, config: dict) -> List[str]:
    have = list(config["queries"])
    want = mix.get("classes", "all")
    if want == "all":
        return have
    missing = [c for c in want if c not in have]
    if missing:
        raise ValueError(f"configuration has no query class {missing}")
    return list(want)


def stream_rng(seed: int, stream: int) -> random.Random:
    # seeds are whole numbers up to a little over 2**31: Random takes any
    return random.Random(f"{seed}/{stream}")


def next_cycle(mix: dict, classes: List[str], rng: random.Random):
    if mix.get("order", "permutation") == "permutation":
        cycle = list(classes)
        rng.shuffle(cycle)
        return cycle
    weights = [float(mix["weights"][c]) for c in classes]
    return rng.choices(classes, weights=weights, k=len(classes))


def _draw(parameters, cls: str, rng: random.Random):
    return None if parameters is None else parameters.draw(cls, rng)


def _one(execute: Callable, cls: str, stream: int, due_s=None,
         on_query=None, params=None) -> Record:
    t0 = time.perf_counter()
    try:
        with (on_query(cls, stream) if on_query is not None
              else contextlib.nullcontext()):
            res = execute(stream, cls, params)
        t1 = time.perf_counter()
        ok = res.state == "FINISHED"
        return Record(cls, stream, t0, t1, ok, res.query_id, res.rows,
                      "" if ok else f"state {res.state}", due_s, params)
    except Exception as e:      # noqa: BLE001 — a failed query is data
        return Record(cls, stream, t0, time.perf_counter(), False,
                      error=f"{type(e).__name__}: {e}"[:300], due_s=due_s,
                      params=params)


def run_closed(mix, classes, seed, seconds, execute, on_query=None,
               cycles=None, stream_tag="", parameters=None):
    """Drive ``clients`` closed streams; returns (t0, records). With
    ``cycles`` set, each stream runs that many cycles (the warm-up);
    else whole cycles until ``seconds`` have passed. ``execute(stream,
    cls, params)`` sends one query."""
    n = int(mix.get("clients", 1))
    think = float(mix.get("think_ms", 0)) / 1000.0
    out = [[] for _ in range(n)]
    gate = threading.Barrier(n + 1)
    t0_box = []

    def stream(i):
        rng = stream_rng(seed, f"{stream_tag}{i}")
        prng = stream_rng(seed, f"params/{stream_tag}{i}")
        gate.wait()
        t0 = t0_box[0]
        done = 0
        while (done < cycles if cycles is not None
               else time.perf_counter() - t0 < seconds):
            for cls in next_cycle(mix, classes, rng):
                out[i].append(_one(execute, cls, i, None, on_query,
                                   _draw(parameters, cls, prng)))
                if think:
                    time.sleep(think)
            done += 1

    threads = [threading.Thread(target=stream, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    t0_box.append(time.perf_counter())
    gate.wait()
    for t in threads:
        t.join()
    return t0_box[0], [r for s in out for r in s]


def run_open(mix, classes, seed, seconds, execute, on_query=None,
             parameters=None):
    """Queries fall due at seeded arrival times over ``seconds``; a pool
    of ``clients`` senders takes them in order. A query's latency counts
    from when it was due, so a stall is paid by all that wait behind it."""
    rng = stream_rng(seed, "arrivals")
    rate = float(mix["rate_per_s"])
    due, t = [], 0.0
    while True:
        t += (rng.expovariate(rate) if mix.get("arrivals") == "poisson"
              else 1.0 / rate)
        if t >= seconds:
            break
        due.append(t)
    plan, crng = [], stream_rng(seed, "classes")
    while len(plan) < len(due):
        plan.extend(next_cycle(mix, classes, crng))
    prng = stream_rng(seed, "params/classes")
    jobs = [(when, cls, _draw(parameters, cls, prng))
            for when, cls in zip(due, plan)]
    lock, out, nxt = threading.Lock(), [], [0]
    t0 = time.perf_counter()

    def sender(i):
        while True:
            with lock:
                if nxt[0] >= len(jobs):
                    return
                when, cls, params = jobs[nxt[0]]
                nxt[0] += 1
            wait = t0 + when - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec = _one(execute, cls, i, t0 + when, on_query, params)
            with lock:
                out.append(rec)

    threads = [threading.Thread(target=sender, args=(i,), daemon=True)
               for i in range(int(mix.get("clients", 8)))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return t0, out


def run_window(mix, classes, seed, seconds, execute, on_query=None,
               parameters=None):
    run = run_closed if mix["loop"] == "closed" else run_open
    return run(mix, classes, seed, seconds, execute, on_query,
               parameters=parameters)


def warm_up(mix, classes, seed, execute, parameters=None):
    """The mix's own shapes before the window: ``warm_cycles`` cycles
    per stream at the mix's concurrency, from a seed of their own (and
    parameters from a tag of their own: ``params/warm<stream>``)."""
    shape = dict(mix, loop="closed", think_ms=0)
    if mix["loop"] == "open":
        shape["clients"] = min(int(mix.get("clients", 8)), 2)
    return run_closed(shape, classes, seed, 0, execute,
                      cycles=int(mix.get("warm_cycles", 1)),
                      stream_tag="warm", parameters=parameters)[1]
