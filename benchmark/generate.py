"""The general generator: deployment and traffic files -> program inputs.

Every mix draws from the seed only the ORDER of a fixed multiset: job
sizes come in exact shares, jobs spread over queues in exact counts, and
arrival gaps are one fixed set of exponential draws, shuffled. So two
seeds give the same amount of work of the same kinds, in another order,
and runs differ by arrangement and not by load.

Quantities are Kubernetes strings, as the program's clients send them.
"""

from __future__ import annotations

import numpy as np

# The fixed stream the arrival gaps are drawn from before the seed
# shuffles them: every seed sees the same gaps.
GAP_STREAM = 20_240_601


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any seed up to and
    beyond 2**32 is valid."""
    return np.random.default_rng([int(seed) & (2**63 - 1), int(stream)])


def exact_counts(n: int, shares) -> np.ndarray:
    """Split n into len(shares) whole counts in the given proportions
    (largest remainders), summing to n exactly."""
    shares = np.asarray(shares, dtype=np.float64)
    raw = n * shares / shares.sum()
    counts = np.floor(raw).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def shuffled_labels(n: int, shares, rng) -> np.ndarray:
    """n labels 0..k-1 in exact proportions, in an order drawn from rng."""
    labels = np.repeat(np.arange(len(shares)), exact_counts(n, shares))
    rng.shuffle(labels)
    return labels


def queue_names(config: dict) -> list[str]:
    q = config["queues"]
    return [f"{q['prefix']}{i:02d}" for i in range(int(q["count"]))]


def priority_factors(config: dict) -> list[float]:
    return [float(x) for x in config["queues"]["priority_factors"]]


def scheduling_config(config: dict):
    """The program's SchedulingConfig for a deployment file: the settings
    the file states, the program's shipped defaults for the rest."""
    from armada_tpu.core.config import PriorityClass, RateLimits, SchedulingConfig

    rl = config["rate_limits"]
    return SchedulingConfig(
        priority_classes={
            name: PriorityClass(name, int(pc["priority"]),
                                preemptible=bool(pc["preemptible"]))
            for name, pc in config["priority_classes"].items()
        },
        default_priority_class=config["default_priority_class"],
        protected_fraction_of_fair_share=float(
            config["protected_fraction_of_fair_share"]
        ),
        rate_limits=RateLimits(
            maximum_scheduling_rate=float(rl["global_rate"]),
            maximum_scheduling_burst=int(rl["global_burst"]),
            maximum_per_queue_scheduling_rate=float(rl["queue_rate"]),
            maximum_per_queue_scheduling_burst=int(rl["queue_burst"]),
        ),
    )


def node_table(config: dict) -> list:
    """[(node id, {resource: quantity})] of the deployment's nodes."""
    spec = config["nodes"]
    res = {"cpu": spec["cpu"], "memory": spec["memory"]}
    return [(f"{spec['prefix']}{i:05d}", res) for i in range(int(spec["count"]))]


def queue_weights(config: dict) -> list:
    """[(queue name, weight)]: a queue's weight is 1 / its priority factor."""
    return [(name, 1.0 / pf)
            for name, pf in zip(queue_names(config), priority_factors(config))]


def nodes(config: dict):
    from armada_tpu.core.types import NodeSpec

    pool = config["pool"]
    return [
        NodeSpec(id=nid, pool=pool, total_resources=dict(res))
        for nid, res in node_table(config)
    ]


def queues(config: dict):
    from armada_tpu.core.types import QueueSpec

    return [
        QueueSpec(name, pf)
        for name, pf in zip(queue_names(config), priority_factors(config))
    ]


class JobStream:
    """Queued jobs of one kind of backlog, made on demand: job k has the
    k-th size of an exact-share sequence shuffled from the seed, the
    k-th queue of an exact round over the queues, and submit time k."""

    def __init__(self, spec: dict, config: dict, seed: int, stream: int,
                 prefix: str, ts0: float = 0.0):
        self.spec = spec
        self.names = queue_names(config)
        self.prefix = prefix
        self.ts0 = float(ts0)
        self.rng = rng_for(seed, stream)
        self.next = 0
        self.sizes = spec["sizes"]

    def take(self, n: int):
        from armada_tpu.core.types import JobSpec

        sizes = shuffled_labels(n, [s["share"] for s in self.sizes], self.rng)
        qidx = shuffled_labels(n, [1.0] * len(self.names), self.rng)
        kinds = [
            (s.get("priority_class", self.spec.get("priority_class", "")),
             {"cpu": s["cpu"], "memory": s["memory"]})
            for s in self.sizes
        ]
        prio = int(self.spec.get("priority", 0))
        out = []
        base = self.next
        for i in range(n):
            pc, req = kinds[sizes[i]]
            out.append(
                JobSpec(
                    id=f"{self.prefix}{base + i:08d}",
                    queue=self.names[qidx[i]],
                    priority=prio,
                    priority_class=pc,
                    requests=req,
                    submitted_ts=self.ts0 + base + i,
                )
            )
        self.next += n
        return out


def running_jobs(config: dict, node_list, seed: int):
    """The deployment's initial running population: `count` jobs of one
    size, dealt round-robin over the nodes and over the queues."""
    from armada_tpu.core.types import JobSpec, RunningJob

    spec = config.get("running")
    if not spec or not int(spec["count"]):
        return []
    names = queue_names(config)
    pc = spec["priority_class"]
    prio = int(config["priority_classes"][pc]["priority"])
    req = {"cpu": spec["cpu"], "memory": spec["memory"]}
    n = int(spec["count"])
    qidx = shuffled_labels(n, [1.0] * len(names), rng_for(seed, 3))
    return [
        RunningJob(
            job=JobSpec(
                id=f"run-{i:08d}",
                queue=names[qidx[i]],
                priority_class=pc,
                requests=req,
                submitted_ts=-float(n - i),
            ),
            node_id=node_list[i % len(node_list)].id,
            scheduled_at_priority=prio,
        )
        for i in range(n)
    ]


def arrival_gaps(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Inter-arrival gaps of an open Poisson loop at `rate_per_s` over
    `seconds`: one fixed multiset of exponential gaps, in an order drawn
    from the seed."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = rng_for(0, GAP_STREAM).exponential(1.0 / rate_per_s, size=n)
    rng_for(seed, 7).shuffle(gaps)
    return gaps
