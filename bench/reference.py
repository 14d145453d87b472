"""Plain per-point reference and the comparisons that decide ``correct``.

The reference recomputes a design point from its labels alone with the
frozen model in `bench.crossflow`: hardware from the architecture
generation engine, one `simulate.predict` per workload phase, then the
scenario's scalar record, eagerly and point by point on the host CPU
device.  It never sees the program's pipeline, bucketing, compile-ahead,
prediction cache or device-resident fold, and it takes nothing the program
made: the architecture comes from the benchmark's configuration file.

The parts of the model that depend on the architecture come from the
module a configuration names under ``"reference"``, where it names one
(see `bench.crossflow`), and otherwise from the frozen defaults.

`Reference.control_record` is the same reference traced once and evaluated with every
floating-point operation carried out in a lower precision; it is the
control that each limit has to fail.
"""

from __future__ import annotations

import dataclasses
import importlib
import itertools
import math
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np

from bench.crossflow import age, lmgraph, records, simulate, techlib, traffic
from bench.crossflow.archconfig import SHAPE_CELLS, ArchConfig
from bench.crossflow.parallelism import Strategy
from bench.crossflow.placement import mesh_system
from bench.crossflow.roofline import PPEConfig

# Largest relative difference allowed between a record the program
# produced on the chip and the reference's.  Its readings are in PERF.md.
REF_RTOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Label:
    arch: str
    cell: str
    mesh: Tuple[int, ...]
    logic: str
    hbm: str
    net: str
    scale: float
    strategy: str

    def key(self) -> str:
        return records.point_key(self.arch, self.cell, self.mesh, self.logic,
                                 self.hbm, self.net, self.scale,
                                 self.strategy)

    @staticmethod
    def of(rec: Mapping) -> "Label":
        return Label(arch=str(rec["arch"]), cell=str(rec["cell"]),
                     mesh=tuple(int(x) for x in str(rec["mesh"]).split("x")),
                     logic=str(rec["logic"]), hbm=str(rec["hbm"]),
                     net=str(rec["net"]), scale=float(rec["scale"]),
                     strategy=str(rec["strategy"]))


def arch_module(config: Mapping):
    """The module a configuration names under ``"reference"``, or None."""
    name = config.get("reference")
    if name is None:
        return None
    path = f"bench.crossflow.{name}"
    try:
        return importlib.import_module(path)
    except ModuleNotFoundError as e:
        if e.name != path:
            raise
        raise ValueError(f"configuration {config.get('name')!r}: "
                         f"\"reference\" names the module {name!r}, but "
                         f"there is no bench/crossflow/{name}.py") from None


def arch_config(config: Mapping, cls=ArchConfig) -> ArchConfig:
    return cls(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in config["arch"].items()})


def budgets(scale: float) -> age.Budgets:
    """`SweepSpec.budgets` with no area or power override."""
    b = age.Budgets.default()
    if scale != 1.0:
        b = dataclasses.replace(
            b, power_w=b.power_w * scale,
            proc_chip_area_mm2=b.proc_chip_area_mm2 * scale,
            node_area_mm2=b.node_area_mm2 * scale)
    return b


class Reference:
    """The reference for one configuration file and one of its grids."""

    def __init__(self, config: Mapping, grid: Mapping):
        mod = arch_module(config)

        def part(name, default):
            return default if mod is None else getattr(mod, name, default)

        self.cfg = arch_config(config, part("ArchConfig", ArchConfig))
        self._build_graph = part("build_graph", lmgraph.build_graph)
        self._strategies = part("candidate_strategies",
                                records.candidate_strategies)
        self.memory = records.MemoryModel(
            part("weight_bytes", records.weight_bytes),
            part("kv_cache_bytes", records.kv_cache_bytes),
            part("kv_shard_degree", records._kv_shard_degree))
        self.arch = str(config["program_arch"])
        self.scenario = str(config["scenario"])
        self.cells = tuple(config["cells"])
        self.grid = grid
        self.ppe = PPEConfig(n_tilings=int(config["n_tilings"]))
        params = dict(grid.get("scenario_params") or {})
        self.scalar_params = {k: v for k, v in params.items()
                              if not isinstance(v, list)}
        self.axes = {k: v for k, v in params.items() if isinstance(v, list)}
        if self.scenario not in ("train", "serving-traffic"):
            raise ValueError(f"no reference for scenario {self.scenario!r}")
        if self.scenario == "train" and (params or len(self.cells) != 1):
            raise ValueError("a train grid has one cell and no params")
        self._graphs: Dict[str, object] = {}
        self._hw: Dict[tuple, age.MicroArch] = {}

    # -- enumeration -----------------------------------------------------
    def cell_ids(self) -> List[str]:
        if self.scenario == "train":
            return [self.cells[0]]
        base = "+".join(self.cells)
        keys = sorted(self.axes)
        return [traffic.encode_variant(base, dict(zip(keys, combo)))
                for combo in itertools.product(*(self.axes[k]
                                                 for k in keys))]

    def keys(self, scales: Iterable[float]) -> List[str]:
        """Every point key a sweep of this grid at ``scales`` must answer."""
        primary = SHAPE_CELLS[self.cells[-1]]
        out = []
        scales = list(scales)
        for cell_id in self.cell_ids():
            for mesh in self.grid["meshes"]:
                for st in self._strategies(self.cfg, primary, tuple(mesh)):
                    for logic, hbm, net, scale in itertools.product(
                            self.grid["logic"], self.grid["hbm"],
                            self.grid["net"], scales):
                        out.append(records.point_key(
                            self.arch, cell_id, tuple(mesh), logic, hbm, net,
                            float(scale), st.name))
        return out

    # -- per-point model -------------------------------------------------
    def hardware(self, lb: Label) -> age.MicroArch:
        k = (lb.logic, lb.hbm, lb.net, lb.scale)
        hw = self._hw.get(k)
        if hw is None:
            hw = self._hw[k] = age.generate(
                techlib.make_tech_config(lb.logic, lb.hbm, lb.net),
                budgets(lb.scale))
        return hw

    def _graph(self, cell: str):
        g = self._graphs.get(cell)
        if g is None:
            g = self._graphs[cell] = self._build_graph(self.cfg,
                                                       SHAPE_CELLS[cell])
        return g

    def phase_cells(self, lb: Label) -> Tuple[str, ...]:
        return tuple(traffic.decode_variant(lb.cell)[0].split("+"))

    def phase_rows(self, lb: Label, hw: age.MicroArch):
        """(phases, 5): total, compute, comm, exposed comm, bubble."""
        st = Strategy.parse(lb.strategy)
        system = mesh_system(lb.mesh)
        out = []
        for cell in self.phase_cells(lb):
            bd = simulate.predict(hw, self._graph(cell), st, system=system,
                                  cfg=self.ppe)
            out.append(jnp.stack([jnp.asarray(x) for x in (
                bd.total_s, bd.compute_s, bd.comm_s, bd.exposed_comm_s,
                bd.pipeline_bubble_s)]))
        return jnp.stack(out)

    def rows(self, lb: Label) -> np.ndarray:
        return np.asarray(self.phase_rows(lb, self.hardware(lb)),
                          dtype=np.float64)

    def record(self, lb: Label, rows: Optional[np.ndarray] = None,
               hw: Optional[age.MicroArch] = None) -> Dict:
        """The reference record; ``rows`` and ``hw`` default to the
        reference's own."""
        hw = hw if hw is not None else self.hardware(lb)
        rows = rows if rows is not None else self.rows(lb)
        st = Strategy.parse(lb.strategy)
        labels = records.label_fields(lb.arch, lb.cell, lb.mesh, lb.logic,
                                      lb.hbm, lb.net, lb.scale, st)
        if self.scenario == "train":
            rec = records.train_record(labels, rows)
        else:
            params = {**self.scalar_params,
                      **traffic.decode_variant(lb.cell)[1]}
            rec = records.serving_traffic_record(
                labels, rows, self.cfg, st, float(hw.dram_capacity),
                self.phase_cells(lb), params, self.memory)
        rec["key"] = lb.key()
        return rec

    def size(self, recs: Sequence[Mapping], qps: float,
             slo: Mapping[str, float]) -> traffic.FleetPlan:
        tm, pol, _ = traffic.split_params(
            {**traffic.PARAM_DEFAULTS, **self.scalar_params})
        return traffic.size_fleet(recs, qps, slo=slo, traffic=tm, policy=pol)

    # -- the lower-precision control ---------------------------------------
    def control_record(self, lb: Label, dtype=jnp.bfloat16) -> Dict:
        """The reference with hardware generation and every phase
        prediction computed in ``dtype``."""
        def fn():
            hw = age.generate(
                techlib.make_tech_config(lb.logic, lb.hbm, lb.net),
                budgets(lb.scale))
            return self.phase_rows(lb, hw), jnp.asarray(hw.dram_capacity)
        rows, cap = eval_in_dtype(jax.make_jaxpr(fn)(), dtype)
        hw = dataclasses.replace(self.hardware(lb),
                                 dram_capacity=float(cap))
        return self.record(lb, np.asarray(rows, dtype=np.float64), hw)


# ---------------------------------------------------------------------------
# A jaxpr evaluated in another floating-point type
# ---------------------------------------------------------------------------


def _floating(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.floating)


# primitives whose body is a closed jaxpr to evaluate in place
_CALLS = {"jit": "jaxpr", "pjit": "jaxpr", "closed_call": "call_jaxpr",
          "custom_jvp_call": "call_jaxpr", "custom_vjp_call": "call_jaxpr"}


def _retype(v, dtype):
    """A primitive's parameter with a floating dtype moved to ``dtype``."""
    if isinstance(v, np.dtype) and jnp.issubdtype(v, jnp.floating):
        return np.dtype(dtype)
    return v


def eval_in_dtype(closed, dtype):
    """Evaluate ``closed`` with every floating input, constant and result
    of every operation rounded to ``dtype``, so each operation computes in
    ``dtype``."""
    def cast(x):
        x = jnp.asarray(x)
        return x.astype(dtype) if _floating(x) else x

    def run(closed, args):
        jaxpr = closed.jaxpr
        env = {}

        def read(v):
            if isinstance(v, jax.extend.core.Literal):
                return cast(v.val)
            return env[v]

        for v, c in zip(jaxpr.constvars, closed.consts):
            env[v] = cast(c)
        for v, a in zip(jaxpr.invars, args):
            env[v] = cast(a)
        for eqn in jaxpr.eqns:
            ins = [read(v) for v in eqn.invars]
            name = eqn.primitive.name
            if name in _CALLS:
                outs = run(eqn.params[_CALLS[name]], ins)
            else:
                if any(isinstance(v, (jax.extend.core.ClosedJaxpr,
                                      jax.extend.core.Jaxpr))
                       for v in eqn.params.values()):
                    raise NotImplementedError(f"control flow {name!r}")
                params = {k: _retype(v, dtype) for k, v in eqn.params.items()}
                outs = eqn.primitive.bind(*ins, **params)
                if not eqn.primitive.multiple_results:
                    outs = [outs]
            for v, o in zip(eqn.outvars, outs):
                env[v] = cast(o)
        return [read(v) for v in jaxpr.outvars]

    return run(closed, [])


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------


def rel_diff(got, want) -> float:
    """Relative difference; a value the program wrote as null stands for a
    non-finite one (JSON has no infinity)."""
    w = float(want)
    if got is None:
        return 0.0 if not math.isfinite(w) else math.inf
    g = float(got)
    if g == w or (math.isnan(g) and math.isnan(w)):
        return 0.0
    if not (math.isfinite(g) and math.isfinite(w)):
        return math.inf
    return abs(g - w) / max(abs(w), 1e-30)


def compare_records(got: Mapping, want: Mapping) -> Tuple[float, int]:
    """(largest relative difference over the numeric fields, number of
    labels, flags and fields that differ or are missing)."""
    worst, wrong = 0.0, 0
    for k, w in want.items():
        if k not in got:
            wrong += 1
        elif isinstance(w, bool) or not isinstance(w, (int, float)):
            wrong += int(got[k] != w)
        elif isinstance(got[k], bool) or not (
                got[k] is None or isinstance(got[k], (int, float))):
            wrong += 1
        else:
            worst = max(worst, rel_diff(got[k], w))
    return worst, wrong


def pareto(recs: Sequence[Mapping], objectives: Sequence[str]) -> List[Mapping]:
    """Non-dominated feasible records (all objectives minimized); records
    equal on every objective are all kept."""
    def vals(r):
        if not r.get("feasible", True) or r.get("slo_ok") is False:
            return None
        try:
            v = [float(r[k]) for k in objectives]
        except (KeyError, TypeError, ValueError):
            return None
        return v if all(math.isfinite(x) for x in v) else None

    pts = [(r, v) for r in recs if (v := vals(r)) is not None]
    out = []
    for r, v in pts:
        if not any(all(a <= b for a, b in zip(u, v))
                   and any(a < b for a, b in zip(u, v)) for _, u in pts):
            out.append(r)
    return out


def dominated_within(point: Mapping, front: Sequence[Mapping],
                     objectives: Sequence[str], rtol: float) -> bool:
    """True if some frontier record is no worse than ``point`` on every
    objective, within ``rtol``; an unusable point needs no cover."""
    if not pareto([point], objectives):
        return True
    v = [float(point[k]) for k in objectives]
    for f in front:
        u = [float(f[k]) if f.get(k) is not None else math.inf
             for k in objectives]
        if all(a <= b * (1.0 + rtol) + 1e-300 for a, b in zip(u, v)):
            return True
    return False
