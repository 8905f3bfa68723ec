"""A deployment's job graphs and their packing onto a shared host pool,
built from a configuration file's data alone.

Semantics (the system's published dataflow model):

- A job is a logical graph of operators. Operator ``o`` runs
  ``parallelism`` tasks; tasks of a job are numbered in the order the
  operators are declared, and task ``i`` of the job sits on local host
  ``i % n_hosts``.
- A ``forward`` edge connects task ``k`` to task ``k``; every other
  partitioner used here connects all tasks to all tasks.
- A failure region is a connected component of the job's task graph.
- Jobs are packed in order into one fleet. Under the ``shared`` host map
  every job's local host ``h`` is pool host ``h``; regions and records
  never cross jobs.
- A ``hash`` edge sends each destination task a fixed share of the
  records: ``max(64 * n_dst, 1024)`` keys with Zipf mass ``1 / k**skew``
  are owned by task ``(k * 2654435761) % n_dst``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    parallelism: int
    service_rate: float
    selectivity: float = 1.0
    is_source: bool = False
    state_bytes_per_task: int = 0
    source_rate: float = 0.0


@dataclasses.dataclass(frozen=True)
class Edge:
    src: str
    dst: str
    partitioner: str = "rebalance"
    key_skew_zipf: float = 0.0


#: partitioners whose routing the reference simulator implements
PARTITIONERS = ("forward", "hash", "rebalance")


@dataclasses.dataclass
class Template:
    """One job graph, with everything a job of it needs that does not
    depend on where the job sits in the fleet."""
    name: str
    ops: list[Op]                 # declaration order
    edges: list[Edge]
    topo: list[str]               # processing order within a tick
    lo: dict[str, int]            # first local task of each op
    n_tasks: int
    local_host: np.ndarray        # (n_tasks,) local host per task
    region: np.ndarray            # (n_tasks,) local region per task
    n_regions: int
    share: dict[tuple[str, str], np.ndarray]   # hash edges

    def op(self, name: str) -> Op:
        return next(o for o in self.ops if o.name == name)

    def out_edges(self, name: str) -> list[Edge]:
        return [e for e in self.edges if e.src == name]

    def span(self, name: str) -> slice:
        return slice(self.lo[name], self.lo[name] + self.op(name).parallelism)

    def source_mask(self) -> np.ndarray:
        m = np.zeros(self.n_tasks, bool)
        for o in self.ops:
            if o.is_source:
                m[self.span(o.name)] = True
        return m


def _topo(ops: list[Op], edges: list[Edge]) -> list[str]:
    """Depth-first order over upstream edges, operators visited in
    declaration order."""
    order: list[str] = []
    seen: set[str] = set()

    def visit(n: str) -> None:
        if n in seen:
            return
        seen.add(n)
        for e in edges:
            if e.dst == n:
                visit(e.src)
        order.append(n)

    for o in ops:
        visit(o.name)
    return order


def _hash_share(n_dst: int, skew: float) -> np.ndarray:
    n_keys = max(n_dst * 64, 1024)
    k = np.arange(1, n_keys + 1, dtype=np.float64)
    mass = 1.0 / k ** skew if skew > 0 else np.ones(n_keys)
    mass = mass / mass.sum()
    owner = (np.arange(n_keys) * 2654435761) % n_dst
    share = np.bincount(owner, weights=mass, minlength=n_dst)
    return share / share.sum()


def template(name: str, spec: dict, n_hosts: int) -> Template:
    ops = [Op(**o) for o in spec["ops"]]
    edges = [Edge(**e) for e in spec["edges"]]
    for e in edges:
        if e.partitioner not in PARTITIONERS:
            raise NotImplementedError(
                f"{name}: partitioner {e.partitioner!r} is not in the "
                f"reference ({PARTITIONERS})")
    lo, off = {}, 0
    for o in ops:
        lo[o.name] = off
        off += o.parallelism
    n_tasks = off
    # regions: union-find over the task graph's channels
    parent = list(range(n_tasks))

    def find(x: int) -> int:
        while parent[x] != x:
            x = parent[x]
        return x

    par = {o.name: o.parallelism for o in ops}
    for e in edges:
        src = range(lo[e.src], lo[e.src] + par[e.src])
        dst = range(lo[e.dst], lo[e.dst] + par[e.dst])
        if e.partitioner == "forward":
            if par[e.src] != par[e.dst]:
                raise ValueError(f"{name}: forward edge {e.src}->{e.dst} "
                                 f"joins unequal parallelism")
            pairs = zip(src, dst)
        else:
            pairs = ((s, d) for s in src for d in dst)
        for s, d in pairs:
            rs, rd = find(s), find(d)
            if rs != rd:
                parent[rs] = rd
    roots = [find(i) for i in range(n_tasks)]
    first: dict[int, int] = {}
    for i, r in enumerate(roots):
        first.setdefault(r, i)
    # regions are numbered by their lowest task
    order = sorted(first, key=first.get)
    rid = {r: k for k, r in enumerate(order)}
    region = np.array([rid[r] for r in roots])
    share = {(e.src, e.dst): _hash_share(par[e.dst], e.key_skew_zipf)
             for e in edges if e.partitioner == "hash"}
    return Template(name, ops, edges, _topo(ops, edges), lo, n_tasks,
                    np.arange(n_tasks) % n_hosts, region, len(order), share)


@dataclasses.dataclass
class Fleet:
    """Jobs of one or more templates packed in order on a shared pool.
    ``groups`` maps a template name to the global indices of its jobs."""
    templates: dict[str, Template]
    job_template: list[str]       # template name per global job index
    groups: dict[str, np.ndarray]
    n_hosts: int
    dt: float
    queue_cap: float

    @property
    def n_jobs(self) -> int:
        return len(self.job_template)

    @property
    def n_tasks(self) -> int:
        return sum(self.templates[t].n_tasks for t in self.job_template)

    def task_slices(self) -> list[slice]:
        """Each job's slice of the fleet-wide task numbering."""
        out, off = [], 0
        for t in self.job_template:
            n = self.templates[t].n_tasks
            out.append(slice(off, off + n))
            off += n
        return out


def fleet(config: dict) -> Fleet:
    """The fleet a configuration file describes."""
    if config.get("host_map", "shared") != "shared":
        raise NotImplementedError("the reference packs jobs on a shared "
                                  "host pool only")
    n_hosts = int(config["n_hosts"])
    templates = {name: template(name, spec, n_hosts)
                 for name, spec in config["graphs"].items()}
    pattern = list(config["job_pattern"])
    jobs = [pattern[j % len(pattern)] for j in range(int(config["n_jobs"]))]
    groups = {name: np.array([j for j, t in enumerate(jobs) if t == name],
                             dtype=int) for name in templates}
    return Fleet(templates, jobs, {k: v for k, v in groups.items()
                                   if len(v)},
                 n_hosts, float(config["dt"]), float(config["queue_cap"]))
