"""Traffic for the benchmark: configurations, pins and the arrival generator.

A configuration file (``bench/configs/<name>.json``) names a scenario of the
program's catalog and pins what the benchmark depends on: the node count,
the padded layer width, the ingress and egress sets, a checksum of the
topology's capacities and, per traffic entry, its weight, layer count and a
checksum of its ``(comp, data)`` cost arrays.  :func:`load_deployment`
builds the scenario and refuses it when any pinned value differs, so a
change to the program's catalog cannot change the traffic under the
benchmark.

A workload file (``bench/workloads/<cell>.json``) holds the cell's traffic
parameters; :func:`epochs` is the one generator that reads them: Poisson
epochs at ``load / mean_service_s / per_epoch`` per simulated second, each
carrying ``per_epoch`` requests, drawn in blocks of ``block_epochs`` that
hold the same work for every seed in an order drawn from the seed.  The
generator is lazy, so a run consumes only the epochs its window reaches.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_workload(name: str) -> dict:
    """The cell's workload file, with its configuration file under
    ``"config_file"``."""
    spec = read_json(BENCH_DIR / "workloads" / f"{name}.json")
    spec["name"] = name
    spec["config_file"] = read_json(BENCH_DIR / "configs"
                                    / f"{spec['config']}.json")
    return spec


def digest(*arrays) -> str:
    """sha256 over the float32 bytes of the given arrays, in order."""
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class Deployment:
    """What one configuration hands the generator and the reference."""

    name: str
    scenario: object              # the program's Scenario, pin-checked
    mu_node: np.ndarray           # [V] float64 copy of the float32 rates
    mu_link: np.ndarray           # [V, V]
    ingress: tuple[int, ...]
    egress: tuple[int, ...]
    weights: np.ndarray           # [K] entry probabilities
    profiles: tuple[tuple[np.ndarray, np.ndarray], ...]   # (comp, data)
    max_layers: int
    mean_service_s: float


def pin_of(scenario) -> dict:
    """The pinned values of a scenario, as the configuration file holds
    them."""
    topo = scenario.topology
    mix = []
    for e in scenario.traffic.entries:
        if e.arch == "synthetic":
            raise ValueError("synthetic entries draw their costs at random "
                             "and cannot be pinned")
        job = e.make_job(np.random.default_rng(0), "pin", 0, 1)
        mix.append({"arch": e.arch, "weight": e.weight,
                    "seq_len": e.seq_len, "batch": e.batch,
                    "num_layers": int(job.comp.shape[0]),
                    "cost_sha256": digest(job.comp, job.data)})
    return {"num_nodes": int(topo.num_nodes),
            "max_layers": int(scenario.max_layers),
            "ingress": [int(i) for i in scenario.ingress],
            "egress": [int(i) for i in scenario.egress],
            "capacity_sha256": digest(topo.mu_node, topo.mu_link),
            "mix": mix}


def load_deployment(config: dict) -> Deployment:
    """Build the configuration's scenario and hold it to the pin."""
    from repro.scenarios import make_scenario
    sc = make_scenario(config["scenario"], seed=int(config["scenario_seed"]),
                       capacity_scale=float(config["capacity_scale"]))
    got, want = pin_of(sc), config["pin"]
    if got != want:
        diff = sorted(k for k in set(got) | set(want)
                      if got.get(k) != want.get(k))
        raise SystemExit(f"bench: configuration {config['name']!r} no longer "
                         f"matches its pin; differing keys: {diff}")
    profiles = tuple((e.make_job(np.random.default_rng(0), "pin", 0, 1).comp,
                      e.make_job(np.random.default_rng(0), "pin", 0, 1).data)
                     for e in sc.traffic.entries)
    w = np.array([e.weight for e in sc.traffic.entries], np.float64)
    return Deployment(
        name=config["name"], scenario=sc,
        mu_node=np.asarray(sc.topology.mu_node, np.float64),
        mu_link=np.asarray(sc.topology.mu_link, np.float64),
        ingress=tuple(sc.ingress), egress=tuple(sc.egress),
        weights=w / w.sum(), profiles=profiles,
        max_layers=int(sc.max_layers),
        mean_service_s=float(config["mean_service_s"]))


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Generator for one stream of one seed; any integer seed (negative or
    past 64 bits included) maps to a valid entropy word."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), int(stream)]))


@dataclasses.dataclass(frozen=True)
class Request:
    """One generated request, as the reference sees it."""

    name: str
    arrival: float
    src: int
    dst: int
    kind: int                      # index into Deployment.profiles
    timed: bool                    # handed to the program in the window


def largest_remainder(shares: np.ndarray, n: int) -> np.ndarray:
    """Whole counts summing to ``n`` in proportion to ``shares``: the floors,
    and one more for the largest fractional parts (ties to the lower
    index)."""
    exact = np.asarray(shares, np.float64) / np.sum(shares) * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def block_work(dep: Deployment, traffic: dict) -> tuple[np.ndarray, list]:
    """The work of one block, the same for every seed: the epochs' gaps
    (the exponential distribution's quantiles at ``(i + 1/2) / block``)
    and the requests' ``(kind, src, dst)``: each mix entry by largest
    remainder of its weight, and within each its requests spread over the
    ingress-egress pairs as evenly as they divide."""
    per, block = int(traffic["per_epoch"]), int(traffic["block_epochs"])
    epoch_rate = float(traffic["load"]) / dep.mean_service_s / per
    gaps = -np.log1p(-(np.arange(block) + 0.5) / block) / epoch_rate
    pairs = [(s, d) for s in dep.ingress for d in dep.egress if d != s]
    work = []
    for kind, n in enumerate(largest_remainder(dep.weights, block * per)):
        for (s, d), c in zip(pairs, largest_remainder(
                np.ones(len(pairs)), int(n))):
            work += [(kind, s, d)] * int(c)
    return gaps, work


def epochs(dep: Deployment, traffic: dict, rng: np.random.Generator, *,
           t0: float, prefix: str, log: dict, timed: bool):
    """Lazy ``(t, [InferenceJob])`` epochs after ``t0``, block by block:
    each block permutes :func:`block_work`'s gaps and requests with
    ``rng``, so every seed offers the same work in another order.

    Each yielded request is entered in ``log`` (name -> :class:`Request`),
    so the harness knows exactly what it handed the program."""
    from repro.core.jobs import InferenceJob
    per = int(traffic["per_epoch"])
    gaps, work = block_work(dep, traffic)
    t, k = float(t0), 0
    while True:
        order = rng.permutation(len(work))
        for e, gap in enumerate(rng.permutation(gaps)):
            t += float(gap)
            jobs = []
            for i in order[e * per:(e + 1) * per]:
                kind, src, dst = work[i]
                name = f"{prefix}{k}"
                k += 1
                comp, data = dep.profiles[kind]
                jobs.append(InferenceJob(name, src, dst, comp, data))
                log[name] = Request(name, t, src, dst, kind, timed)
            yield t, jobs
