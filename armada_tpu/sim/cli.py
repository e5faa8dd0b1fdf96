"""Simulator CLI: run cluster+workload YAML specs through the real scheduler.

The cmd/simulator equivalent (/root/reference/cmd/simulator/cmd/root.go:18):

  python -m armada_tpu.sim.cli --clusters clusters.yaml --workload load.yaml
      [--config scheduling.yaml] [--backend kernel] [--seed 0]

Cluster YAML:                      Workload YAML:
  name: cluster-1                    queues:
  pool: default                        - name: queue-a
  nodeTemplates:                         priorityFactor: 1.0
    - count: 100                         jobTemplates:
      cpu: "32"                            - id: basic
      memory: 1024Gi                         number: 1000
                                             cpu: "1"
                                             memory: 4Gi
                                             runtimeMinimum: 60
                                             runtimeTailMean: 30
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import yaml

from ..core.config import SchedulingConfig
from .simulator import (
    ClusterSpec,
    JobTemplate,
    NodeTemplate,
    QueueSpecSim,
    ShiftedExponential,
    Simulator,
    WorkloadSpec,
)


def load_cluster(path: str) -> ClusterSpec:
    with open(path) as f:
        doc = yaml.safe_load(f)
    return ClusterSpec(
        name=doc.get("name", "cluster"),
        pool=doc.get("pool", "default"),
        node_templates=tuple(
            NodeTemplate(
                count=int(t["count"]),
                cpu=str(t.get("cpu", "32")),
                memory=str(t.get("memory", "1024Gi")),
                gpu=str(t.get("gpu", "0")),
                labels=dict(t.get("labels", {})),
            )
            for t in doc.get("nodeTemplates", [])
        ),
    )


def load_workload(path: str) -> WorkloadSpec:
    with open(path) as f:
        doc = yaml.safe_load(f)
    queues = []
    for q in doc.get("queues", []):
        templates = []
        for t in q.get("jobTemplates", []):
            templates.append(
                JobTemplate(
                    id=str(t.get("id", "tmpl")),
                    number=int(t.get("number", 1)),
                    cpu=str(t.get("cpu", "1")),
                    memory=str(t.get("memory", "4Gi")),
                    gpu=str(t.get("gpu", "0")),
                    priority_class=t.get("priorityClassName", ""),
                    queue_priority=int(t.get("queuePriority", 0)),
                    runtime=ShiftedExponential(
                        minimum=float(t.get("runtimeMinimum", 60)),
                        tail_mean=float(t.get("runtimeTailMean", 0)),
                    ),
                    submit_time=float(t.get("submitTime", 0)),
                    gang_cardinality=int(t.get("gangCardinality", 0)),
                    node_selector=dict(t.get("nodeSelector", {})),
                )
            )
        queues.append(
            QueueSpecSim(
                q["name"], float(q.get("priorityFactor", 1.0)), tuple(templates)
            )
        )
    return WorkloadSpec(queues=tuple(queues))


def main(argv=None):
    p = argparse.ArgumentParser(prog="armada-tpu-simulator")
    p.add_argument("--clusters", nargs="+", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--config")
    p.add_argument("--backend", default="oracle", choices=["oracle", "kernel"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cycle-interval", type=float, default=10.0)
    p.add_argument("--json", action="store_true", help="machine-readable output")
    args = p.parse_args(argv)

    from ..utils.platform import setup_jax

    setup_jax()
    config = SchedulingConfig()
    if args.config:
        with open(args.config) as f:
            doc = yaml.safe_load(f) or {}
        config = SchedulingConfig.from_dict(doc.get("scheduling", doc))

    sim = Simulator(
        [load_cluster(c) for c in args.clusters],
        load_workload(args.workload),
        config,
        backend=args.backend,
        seed=args.seed,
        cycle_interval=args.cycle_interval,
    )
    wall0 = time.time()
    res = sim.run()
    wall = time.time() - wall0
    out = {
        "finished_jobs": res.finished_jobs,
        "total_jobs": res.total_jobs,
        "makespan_s": res.makespan,
        "preemptions": res.preemptions,
        "cycles": res.cycles,
        "wall_s": round(wall, 2),
    }
    if args.json:
        print(json.dumps(out))
    else:
        for k, v in out.items():
            print(f"{k}: {v}")
    return 0 if res.finished_jobs + res.preemptions >= res.total_jobs else 1


if __name__ == "__main__":
    sys.exit(main())
