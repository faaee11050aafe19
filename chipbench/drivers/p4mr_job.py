"""Driver: p4mr word-count jobs, one closed-loop client, ``plan.run`` as users call it.

Set-up draws a pool of count-vector sets from the seed (one vector per
server, ``words_per_server`` log-uniform words over a vocabulary of
``width`` ids), compiles the job once with a ``p4mr.Session`` on the
configuration's torus, and runs one job to warm up. A unit of work is one
job: ``plan.run(inputs, backend="jax")`` on the next set of the pool,
through to its collected counts on the host. The check compares every job
of the window with ``reference.wordcount.job_counts``, exactly.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import wordcount as ref
from chipbench.traffic import zipf


def build_job(servers: int, width: int):
    """Stores ``s<i>`` on ``d<i>``, each ``key_by(servers)``, one SUM, collected on d0."""
    from repro import p4mr

    job = p4mr.job("wordcount")
    keyed = [job.store(f"s{i}", host=f"d{i}", items=width).key_by(servers)
             for i in range(servers)]
    keyed[0].reduce("SUM", *keyed[1:], label="COUNTS").collect("d0", label="OUT")
    return job


class Run:
    def __init__(self, ctx):
        from repro import p4mr
        from repro.compiler import CostModel
        from repro.core.topology import TorusTopology

        p, dep = ctx.traffic, ctx.config["deployment"]
        self.servers, self.width = dep["servers"], dep["vocabulary"]
        rng = np.random.Generator(np.random.Philox(key=ctx.seed))
        self.pool = [zipf.server_histograms(rng, self.width, dep["words_per_server"],
                                            self.servers) for _ in range(p["pool"])]
        sess = p4mr.Session(TorusTopology(dims=tuple(dep["torus"])),
                            cost_model=CostModel(switch_memory_bytes=dep["switch_memory_bytes"]))
        self.plan = sess.compile(build_job(self.servers, self.width))
        self.jobs = 0
        self.answers: list[tuple[int, np.ndarray]] = []
        self.work = 1  # one job per unit
        self.counts = {}
        self._run(0)  # warm-up

    def _run(self, k: int) -> np.ndarray:
        inputs = {f"s{i}": h for i, h in enumerate(self.pool[k])}
        return self.plan.run(inputs, backend="jax")["OUT"]

    def unit(self) -> None:
        k = self.jobs % len(self.pool)
        self.answers.append((k, self._run(k)))
        self.jobs += 1

    def release(self) -> None:
        self.plan = None

    def check(self):
        """Words whose collected count differs from the reference, in the
        worst job; exact, so the limit is 0."""
        want = [ref.job_counts(h) for h in self.pool]
        wrong = [int(np.count_nonzero(got != want[k])) for k, got in self.answers]
        compared = [{"name": "wrong_counts", "value": max(wrong), "limit": 0}]
        return compared, sum(1 for w in wrong if w)


def setup(ctx) -> Run:
    return Run(ctx)


def controls(config: dict, traffic: dict, cell: dict, seed: int, devices) -> dict:
    """The control: the job's SUM in bfloat16, over the seed's pool, in the
    number the check compares."""
    dep = config["deployment"]
    rng = np.random.Generator(np.random.Philox(key=seed))
    pool = [zipf.server_histograms(rng, dep["vocabulary"], dep["words_per_server"],
                                   dep["servers"]) for _ in range(traffic["pool"])]
    wrong = [int(np.count_nonzero(ref.job_counts_bf16(h) != ref.job_counts(h))) for h in pool]
    return {"control": {"wrong_counts": max(wrong)}}
