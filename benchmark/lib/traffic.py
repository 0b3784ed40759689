"""One general traffic generator: a mix is a data file of parameters.

A traffic file names the request (method and parameters), the loop (``closed``
with ``clients``, or ``open`` with ``rate`` in requests per second and
``burst``), where scenarios come from (``aamas`` ids, or ``corpus`` with a
name and a mix) and how they repeat (``round_robin``, ``fixed:K``,
``zipf:S``), optionally a share of requests that carry other parameters
(``greedy``: every ``every``-th request from ``offset`` on), and what set-up
has to warm (``warm``).

A run's requests are an endless stream drawn from ``--seed``: the scenarios in
an order of the seed's own (a permutation, gone through over and over, so that
every seed sends the same set of scenarios), and a new request seed each.  A
greedy request is an extra one, on a scenario that set-up found it answered
on.  The program sees only the payloads.
"""

from __future__ import annotations

import itertools
import json
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple


def scenario_refs(spec: Dict[str, Any]) -> List[str]:
    if spec["source"] == "aamas":
        return [f"aamas:{k}" for k in spec["ids"]]
    if spec["source"] == "corpus":
        return [f"corpus:{spec['name']}:{k}" for k in spec["ids"]]
    raise ValueError(f"unknown scenario source {spec['source']!r}")


def _order(spec: Dict[str, Any], n: int, rng: random.Random) -> Iterator[int]:
    """Indices into the scenario list, in an order drawn from the seed."""
    kind, _, arg = str(spec.get("order", "round_robin")).partition(":")
    if kind in ("round_robin", "fixed"):
        k = n if kind == "round_robin" else max(1, min(n, int(arg or 1)))
        turn = rng.sample(range(n), k)
        return (turn[i % k] for i in itertools.count())
    if kind == "zipf":
        ranks = rng.sample(range(n), n)
        weights = [1.0 / (rank + 1) ** float(arg or 1.0) for rank in range(n)]
        return (rng.choices(ranks, weights)[0] for _ in itertools.count())
    raise ValueError(f"unknown scenario order {spec.get('order')!r}")


def _streams(traffic: Dict[str, Any], seed: int, draw: str):
    order_rng = random.Random(f"traffic:{traffic['name']}:{int(seed)}")
    seed_rng = random.Random(f"traffic:{traffic['name']}:{int(seed)}:{draw}")
    refs = scenario_refs(traffic["scenarios"])
    return refs, _order(traffic["scenarios"], len(refs), order_rng), seed_rng


def greedy_scenarios(traffic: Dict[str, Any], seed: int) -> List[Optional[str]]:
    """The scenarios a mix's greedy requests may use, in the seed's order:
    set-up takes the first on which a greedy request is answered (greedy
    decoding on random weights can run into white space alone, which the
    method refuses).  [None] for a mix with no greedy requests."""
    if not traffic.get("greedy"):
        return [None]
    refs, order, _ = _streams(traffic, seed, "window")
    out: List[Optional[str]] = []
    for index in order:
        if refs[index] in out or len(out) == len(refs):
            break
        out.append(refs[index])
    return out


def bodies(traffic: Dict[str, Any], seed: int, draw: str = "window",
           greedy_scenario: Optional[str] = None) -> Iterator[Dict[str, Any]]:
    """The endless stream of request bodies this mix sends under ``seed`` (no
    request id yet).  The order of scenarios and the places of the greedy
    requests follow the seed alone; the request seeds follow the seed and
    ``draw``, so that set-up can send the window's kinds of request without
    sending the window's requests.  A greedy request is one more request on
    ``greedy_scenario`` and takes no turn of the scenarios' order."""
    refs, order, seed_rng = _streams(traffic, seed, draw)
    request = traffic["request"]
    greedy = traffic.get("greedy")
    if greedy and greedy_scenario is None:
        greedy_scenario = greedy_scenarios(traffic, seed)[0]
    for index in itertools.count():
        params = dict(request["params"])
        if greedy and index % int(greedy["every"]) == int(greedy.get("offset", 0)):
            params.update(greedy["params"])
            scenario = greedy_scenario
        else:
            scenario = refs[next(order)]
        yield {
            "scenario": scenario,
            "method": request["method"],
            "params": params,
            "seed": seed_rng.randrange(1, 2 ** 31 - 1),
        }


def paper_shaped(traffic: Dict[str, Any], payload: Dict[str, Any]) -> bool:
    """Whether a request carries the mix's own parameters (not the greedy
    ones, which exist for the output check alone)."""
    return payload["params"] == traffic["request"]["params"]


def warm_bodies(traffic: Dict[str, Any], seed: int,
                greedy_scenario: Optional[str] = None) -> List[Dict[str, Any]]:
    """One request of each kind (scenario and parameters) among the first
    ``warm.requests`` of the window's stream, with request seeds of its own
    draw: what set-up sends so that the window's programs exist."""
    ahead = int(traffic.get("warm", {}).get(
        "requests", 2 * len(scenario_refs(traffic["scenarios"]))))
    out: Dict[str, Dict[str, Any]] = {}
    stream = bodies(traffic, seed, "warm", greedy_scenario)
    for body in itertools.islice(stream, ahead):
        out.setdefault(json.dumps([body["scenario"], body["params"]],
                                  sort_keys=True), body)
    return list(out.values())


def payloads(stream: Iterator[Dict[str, Any]],
             tag: str = "bench") -> Iterator[Dict[str, Any]]:
    """Each body of ``stream`` under a request id of its own."""
    for index, body in enumerate(stream):
        yield dict(body, request_id=f"{tag}-{index}")


def post(base_url: str, payload: Dict[str, Any], timeout_s: float) -> Tuple[int, Any]:
    request = urllib.request.Request(
        base_url + "/v1/consensus", data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(request, timeout=timeout_s) as response:
            return response.status, json.loads(response.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"null")


def get_json(base_url: str, path: str) -> Any:
    with urllib.request.urlopen(base_url + path, timeout=30.0) as response:
        return json.loads(response.read())


class Sent:
    """One request as the client saw it.  ``due`` is when it was to be sent
    (an open loop's schedule; equal to ``sent`` in a closed loop)."""

    def __init__(self, index, payload, due, sent):
        self.index, self.payload, self.due, self.sent = index, payload, due, sent
        self.done: Optional[float] = None
        self.status: Optional[int] = None
        self.body: Any = None
        self.error: Optional[str] = None

    @property
    def seconds(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


def drive(base_url: str, traffic: Dict[str, Any], stream: Iterator[Dict[str, Any]],
          stop: Callable[[int, float], bool], timeout_s: float = 600.0) -> List[Sent]:
    """Send from ``stream`` until ``stop(sent so far, seconds since start)``
    says so, wait for every answer, and return the requests in send order.
    Requests in flight when sending stops finish and count."""
    loop = traffic["loop"]
    lock = threading.Lock()
    out: List[Sent] = []
    start = time.perf_counter()

    def fire(sent: Sent) -> None:
        try:
            sent.status, sent.body = post(base_url, sent.payload, timeout_s)
        except Exception as exc:  # a dead socket is a failed request
            sent.error = f"{type(exc).__name__}: {exc}"
        sent.done = time.perf_counter()

    def take(due: Optional[float] = None) -> Optional[Sent]:
        with lock:
            now = time.perf_counter()
            if stop(len(out), now - start):
                return None
            sent = Sent(len(out), next(stream), now if due is None else due, now)
            out.append(sent)
            return sent

    if loop["kind"] == "closed":
        def client() -> None:
            while True:
                sent = take()
                if sent is None:
                    return
                fire(sent)

        threads = [threading.Thread(target=client, name=f"client-{i}")
                   for i in range(int(loop["clients"]))]
        for thread in threads:
            thread.start()
    elif loop["kind"] == "open":
        rate, burst = float(loop["rate"]), int(loop.get("burst", 1))
        threads = []
        for i in itertools.count():
            due = start + (i // burst) * burst / rate
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sent = take(due)
            if sent is None:
                break
            thread = threading.Thread(target=fire, args=(sent,), name=f"open-{i}")
            thread.start()
            threads.append(thread)
    else:
        raise ValueError(f"unknown loop kind {loop['kind']!r}")
    for thread in threads:
        thread.join()
    return out
