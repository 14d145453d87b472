"""Micro-Architecture Generator Engine (AGE) — DeepFlow paper §4.

Given (technology config, architecture template, area/power/perimeter budget
breakdown), derive the micro-architectural parameters consumed by the
performance prediction engine:

  * compute throughput (paper eq. 1, voltage-frequency scaled),
  * per-level on-chip memory capacity + bandwidth (eqs. 2-3, crossbar +
    controller overheads included),
  * main-memory capacity + bandwidth (eq. 4),
  * intra- and inter-package network bandwidth.

All arithmetic is written in `jax.numpy` so the whole AGE is differentiable
w.r.t. the budget fractions — this is what lets the Search-and-Optimization
Engine (repro.core.soe) use *exact* `jax.grad` gradients instead of the
paper's black-box numeric ones (a beyond-paper improvement recorded in
DESIGN.md). Set ``discrete=True`` to apply floors (reporting mode).

Reporting callers with concrete budgets use `generate_rows`, which runs
the same formulas for many hardware rows in one compiled, batched call:
run eagerly, `generate` costs one device dispatch per scalar operation.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import techlib
from repro.core.techlib import TechConfig

# Component keys, in the order used by budget vectors (SOE optimizes this
# flat vector; keep the order stable).
COMPONENTS = ("core", "l2", "l1", "l0", "dram", "net_intra", "net_inter")
# Perimeter is only consumed by off-die interfaces.
PERIM_COMPONENTS = ("dram", "net_intra", "net_inter")


@dataclasses.dataclass(frozen=True)
class Budgets:
    """Hardware resource allocation (paper §4.3, Fig. 4)."""

    node_area_mm2: float = 1230.0       # package/substrate budget
    proc_chip_area_mm2: float = 815.0   # compute die budget
    power_w: float = 300.0
    # fractional breakdowns over COMPONENTS; need not sum exactly to 1
    area_frac: Dict[str, float] = dataclasses.field(default_factory=dict)
    power_frac: Dict[str, float] = dataclasses.field(default_factory=dict)
    perim_frac: Dict[str, float] = dataclasses.field(default_factory=dict)

    @staticmethod
    def default() -> "Budgets":
        return Budgets(
            area_frac={"core": 0.35, "l2": 0.14, "l1": 0.10, "l0": 0.20,
                       "dram": 0.05, "net_intra": 0.06, "net_inter": 0.10},
            power_frac={"core": 0.50, "l2": 0.12, "l1": 0.10, "l0": 0.08,
                        "dram": 0.12, "net_intra": 0.03, "net_inter": 0.05},
            perim_frac={"dram": 0.50, "net_intra": 0.20, "net_inter": 0.30},
        )

    def as_vector(self) -> jnp.ndarray:
        """Flatten to the SOE parameter vector W = {A_i, P_i, R_i} (paper §7)."""
        a = [self.area_frac.get(c, 0.0) for c in COMPONENTS]
        p = [self.power_frac.get(c, 0.0) for c in COMPONENTS]
        r = [self.perim_frac.get(c, 0.0) for c in PERIM_COMPONENTS]
        return jnp.asarray(a + p + r, dtype=jnp.float32)

    @staticmethod
    def from_vector(w, like: "Budgets") -> "Budgets":
        n = len(COMPONENTS)
        a = {c: w[i] for i, c in enumerate(COMPONENTS)}
        p = {c: w[n + i] for i, c in enumerate(COMPONENTS)}
        r = {c: w[2 * n + i] for i, c in enumerate(PERIM_COMPONENTS)}
        return Budgets(node_area_mm2=like.node_area_mm2,
                       proc_chip_area_mm2=like.proc_chip_area_mm2,
                       power_w=like.power_w,
                       area_frac=a, power_frac=p, perim_frac=r)


@dataclasses.dataclass(frozen=True)
class MicroArch:
    """AGE output: the parameters the performance model consumes.

    Bandwidths are aggregate bytes/s per accelerator node; capacities bytes.
    Fields may be python floats or jnp scalars (when traced by the SOE).
    """

    tech: TechConfig
    n_mcu: object
    core_frequency: object
    compute_throughput: object          # flops/s, after max_utilization derate
    mem_capacity: tuple                 # (L0, L1, L2) bytes
    mem_bw: tuple                       # (L0, L1, L2) bytes/s
    mem_latency: tuple                  # (L0, L1, L2) s
    dram_capacity: object
    dram_bw: object
    dram_latency: float
    net_intra_bw: object                # per-link effective bytes/s
    net_intra_links: object
    net_intra_latency: float
    net_inter_bw: object                # per-link effective bytes/s
    net_inter_links: object
    net_inter_latency: float

    def memory_hierarchy(self):
        """(capacity, bw, latency) per level, L0 (regs) .. L3 (DRAM)."""
        caps = list(self.mem_capacity) + [self.dram_capacity]
        bws = list(self.mem_bw) + [self.dram_bw]
        lats = list(self.mem_latency) + [self.dram_latency]
        return caps, bws, lats


def _smooth_floor(x, discrete: bool):
    return jnp.floor(x) if discrete else x


def _identity(x):
    return x


def _rounded(x):
    """``x`` as its own rounded float32 value: `_derive`'s ``sep`` for a
    compiled caller (an identity the compiler cannot see through)."""
    return jnp.where(x == x, x, jnp.nan)


def _power_limited_voltage(p_budget, p_nominal, vnom, vth, vmin, v_span,
                           sep=_identity):
    """Differentiable fixed-point solve of P(V)=Pb (see techlib docstring).

    P(V) = Pnom * (V/Vnom)^2 * (V-Vth)/(Vnom-Vth); 20 unrolled iterations of
    V <- Vth + (Vnom-Vth) * (Pb/Pnom) * (Vnom/V)^2, clipped to [vmin, vnom].
    ``v_span`` is Vnom-Vth, formed by the caller in the inputs' precision;
    ``sep`` as in `_derive`.
    """
    ratio = jnp.clip(p_budget / jnp.maximum(p_nominal, 1e-12), 1e-6, 1.0)
    v = jnp.asarray(vnom, dtype=jnp.float32)
    for _ in range(20):
        v_new = vth + sep(v_span * ratio * (vnom / jnp.maximum(v, 1e-6)) ** 2)
        v = jnp.clip(v_new, vmin, vnom)
    return v


_LEVELS = ("l0", "l1", "l2")
_NETS = ("net_intra", "net_inter")


def _input_terms(tech: TechConfig, budgets: Budgets) -> Dict[str, object]:
    """The first half of the AGE: arithmetic on the budgets and the
    technology alone, before any `jnp` operation.

    With Python floats (the reporting path) this runs in float64 on the
    host and `_derive` takes each term into float32, exactly where the
    AGE has always rounded; traced callers get the same expressions on
    their tracers.  Keep every term an expression of the inputs only.
    """
    af, pf, rf = budgets.area_frac, budgets.power_frac, budgets.perim_frac
    chip_area = budgets.proc_chip_area_mm2
    power = budgets.power_w
    c, d = tech.compute, tech.dram
    t = {
        "chip_area": chip_area,
        # core (paper §4.4.1)
        "core_units": af.get("core", 0.0) * chip_area / c.nominal_area_mm2,
        "p_core": pf.get("core", 0.0) * power,
        "mcu_power": c.nominal_power,
        "v_nom": c.nominal_voltage,
        "v_th": c.threshold_voltage,
        "v_min": c.minimum_voltage,
        "v_span": c.nominal_voltage - c.threshold_voltage,
        "f_nom": c.nominal_frequency,
        "flops_per_cycle": c.nominal_flops_per_cycle,
        "max_util": c.max_utilization,
        # main memory (paper §4.4.3)
        "rf_dram": rf.get("dram", 0.0),
        "dram_links_per_mm": d.links_per_mm,
        "dev_by_area": ((budgets.node_area_mm2 - chip_area)
                        / d.device_area_mm2),
        "dev_by_ctrl": (af.get("dram", 0.0) * chip_area
                        / d.controller_io_area_mm2),
        "links_per_device": d.links_per_device,
        "dev_bytes": d.device_capacity_bytes,
        "dev_bw": d.device_bw_bytes,
        "dev_static_w": d.static_power_per_device_w,
        "p_dram": pf.get("dram", 0.0) * power,
        "dram_energy_per_byte": d.dynamic_energy_per_bit * 8.0,
    }
    for name in _LEVELS:            # on-chip levels (paper §4.4.2)
        m: techlib.OnChipMemTech = getattr(tech, name)
        t[f"a_{name}"] = af.get(name, 0.0) * chip_area
        t[f"p_{name}"] = pf.get(name, 0.0) * power
        t[f"bank_area_{name}"] = (m.bank_area_mm2
                                  + m.controller_area_per_bank_mm2)
        t[f"xbar_area_{name}"] = m.xbar_area_per_port_mm2
        t[f"bank_bytes_{name}"] = m.bank_capacity_bytes
        t[f"static_per_bit_{name}"] = m.static_power_per_bit
        t[f"ctrl_power_{name}"] = m.controller_power_per_bank_w
        t[f"energy_per_bit_{name}"] = (m.dynamic_energy_per_bit
                                       + m.xbar_energy_per_bit)
    for key in _NETS:               # networks (paper §4.4.4)
        n: techlib.NetworkTech = getattr(tech, key)
        t[f"links_by_area_{key}"] = (af.get(key, 0.0) * chip_area
                                     / n.area_per_link_mm2)
        t[f"rf_{key}"] = rf.get(key, 0.0)
        t[f"links_per_mm_{key}"] = n.links_per_mm
        t[f"link_bw_{key}"] = n.nominal_bw_per_link_bytes
        t[f"bw_pow_{key}"] = (pf.get(key, 0.0) * power
                              / (n.nominal_energy_per_bit * 8.0))
    return t


def _derive(t, discrete: bool, sep=_identity) -> Dict[str, object]:
    """The second half of the AGE: every `jnp` operation, on the terms of
    `_input_terms` (Python floats, tracers, or a batched row).

    ``sep`` wraps each product that a sum consumes.  A compiled caller
    passes `_rounded`, so that the compiler cannot contract the pair into
    a fused multiply-add, which rounds once where the eager operations
    round twice.
    """
    perimeter = 4.0 * jnp.sqrt(t["chip_area"])

    # ---- Core (paper §4.4.1, eq. 1) ------------------------------------
    p_core = t["p_core"]
    n_mcu = _smooth_floor(t["core_units"], discrete)
    n_mcu = jnp.maximum(n_mcu, 1e-3)
    p_nominal = n_mcu * t["mcu_power"]
    v_op = _power_limited_voltage(p_core, p_nominal, t["v_nom"], t["v_th"],
                                  t["v_min"], t["v_span"], sep)
    f_op = t["f_nom"] * (v_op - t["v_th"]) / t["v_span"]
    # If even Vmin overflows the power budget, shed MCUs (paper: "reduce the
    # number of MCUs till we satisfy the total power budget").
    p_at_vmin = (n_mcu * t["mcu_power"]
                 * (v_op / t["v_nom"]) ** 2
                 * (f_op / t["f_nom"]))
    shed = jnp.clip(p_core / jnp.maximum(p_at_vmin, 1e-12), 0.0, 1.0)
    n_eff = n_mcu * shed
    n_eff = _smooth_floor(n_eff, discrete)
    n_eff = jnp.maximum(n_eff, 1e-3)
    throughput = (n_eff * t["flops_per_cycle"] * f_op
                  * t["max_util"])                           # eq. 1 (+derate)
    out = {"n_mcu": n_eff, "core_frequency": f_op,
           "compute_throughput": throughput}

    # ---- On-chip memory levels (paper §4.4.2, eqs. 2-3) -----------------
    n_clients = n_eff     # crossbar ports scale with #MCUs (paper §9.1 insight)
    for name in _LEVELS:
        per_bank = (t[f"bank_area_{name}"]
                    + sep(n_clients * t[f"xbar_area_{name}"]))
        n_banks = _smooth_floor(t[f"a_{name}"] / per_bank, discrete)
        n_banks = jnp.maximum(n_banks, 1e-3)
        capacity = n_banks * t[f"bank_bytes_{name}"]
        p_static = (sep(t[f"static_per_bit_{name}"] * capacity * 8.0)
                    + sep(n_banks * t[f"ctrl_power_{name}"]))  # eq. 2
        p_dyn = jnp.maximum(t[f"p_{name}"] - p_static, 0.0)
        bw_bits = p_dyn / t[f"energy_per_bit_{name}"]
        out[f"mem_bw_{name}"] = bw_bits / 8.0                 # eq. 3
        out[f"mem_capacity_{name}"] = capacity

    # ---- Main memory (paper §4.4.3, eq. 4) ------------------------------
    perim_links = t["rf_dram"] * perimeter * t["dram_links_per_mm"]
    n_dev = jnp.minimum(
        jnp.minimum(t["dev_by_area"], t["dev_by_ctrl"]),
        perim_links / t["links_per_device"])                  # eq. 4
    n_dev = jnp.maximum(_smooth_floor(n_dev, discrete), 1e-3)
    out["dram_capacity"] = n_dev * t["dev_bytes"]
    bw_nom = n_dev * t["dev_bw"]
    p_static_dram = n_dev * t["dev_static_w"]
    p_dyn_dram = jnp.maximum(t["p_dram"] - sep(p_static_dram), 0.0)
    out["dram_bw"] = jnp.minimum(bw_nom,
                                 p_dyn_dram / t["dram_energy_per_byte"])

    # ---- Networks (paper §4.4.4) ----------------------------------------
    for key in _NETS:
        n_links = jnp.minimum(t[f"links_by_area_{key}"],
                              t[f"rf_{key}"] * perimeter
                              * t[f"links_per_mm_{key}"])
        n_links = jnp.maximum(_smooth_floor(n_links, discrete), 1e-3)
        bw_nom_total = n_links * t[f"link_bw_{key}"]
        bw_total = jnp.minimum(bw_nom_total, t[f"bw_pow_{key}"])
        out[f"{key}_bw"] = bw_total / n_links       # effective per-link BW
        out[f"{key}_links"] = n_links
    return out


def _microarch(tech: TechConfig, out: Dict[str, object]) -> MicroArch:
    return MicroArch(
        tech=tech,
        n_mcu=out["n_mcu"],
        core_frequency=out["core_frequency"],
        compute_throughput=out["compute_throughput"],
        mem_capacity=tuple(out[f"mem_capacity_{n}"] for n in _LEVELS),
        mem_bw=tuple(out[f"mem_bw_{n}"] for n in _LEVELS),
        mem_latency=tuple(getattr(tech, n).latency_s for n in _LEVELS),
        dram_capacity=out["dram_capacity"],
        dram_bw=out["dram_bw"],
        dram_latency=tech.dram.access_latency_s,
        net_intra_bw=out["net_intra_bw"],
        net_intra_links=out["net_intra_links"],
        net_intra_latency=tech.net_intra.link_latency_s,
        net_inter_bw=out["net_inter_bw"],
        net_inter_links=out["net_inter_links"],
        net_inter_latency=tech.net_inter.link_latency_s,
    )


def generate(tech: TechConfig, budgets: Budgets,
             discrete: bool = True) -> MicroArch:
    """Run the AGE (paper §4.4): budgets + tech -> micro-arch parameters."""
    out = _derive(_input_terms(tech, budgets), discrete)
    if not isinstance(out["n_mcu"], jax.core.Tracer):
        _count(eager_rows=1)        # one dispatch per scalar operation
    return _microarch(tech, out)


# ---------------------------------------------------------------------------
# Batched reporting path: many hardware rows in one compiled call
# ---------------------------------------------------------------------------

# the input terms' order (the same for every technology)
_TERMS = tuple(_input_terms(techlib.make_tech_config(), Budgets.default()))
_OUTPUTS = (("n_mcu", "core_frequency", "compute_throughput")
            + tuple(f"mem_{f}_{n}" for n in _LEVELS
                    for f in ("bw", "capacity"))
            + ("dram_capacity", "dram_bw")
            + tuple(f"{k}_{f}" for k in _NETS for f in ("bw", "links")))

_STATS = {"calls": 0, "rows": 0, "padded_rows": 0, "eager_rows": 0}
_STATS_LOCK = threading.Lock()


def _count(**deltas) -> None:
    with _STATS_LOCK:
        for k, v in deltas.items():
            _STATS[k] += v


def age_stats() -> Dict[str, int]:
    """How often the batched AGE engaged: ``calls`` to `generate_rows`,
    the ``rows`` they resolved and the ``padded_rows`` they computed
    (rows padded to a power of two), and ``eager_rows``, concrete
    `generate` calls that ran one device dispatch per scalar operation."""
    with _STATS_LOCK:
        return dict(_STATS)


@jax.jit
def age_rows(terms):
    """(N, len(_TERMS)) input terms -> (N, len(_OUTPUTS)) discrete AGE."""
    def row(v):
        out = _derive(dict(zip(_TERMS, v)), True, sep=_rounded)
        return jnp.stack([out[k] for k in _OUTPUTS])
    return jax.vmap(row)(terms)


def generate_rows(techs: Sequence[TechConfig],
                  budgets: Sequence[Budgets]) -> List[MicroArch]:
    """`generate(tech, budgets, discrete=True)` for many rows in one
    compiled call on the default device and one transfer back.

    The input terms are formed on the host in float64 and rounded to
    float32, as the eager path rounds them, so every row equals its eager
    `generate` and its floors come out the same.  Technology leaves are
    inputs, not constants, so one executable per padded row count serves
    every technology.  The returned fields are host `np.float32` scalars.
    """
    n = len(techs)
    if n != len(budgets):
        raise ValueError(f"{n} techs for {len(budgets)} budgets")
    if n == 0:
        return []
    n_pad = 1 << (n - 1).bit_length()
    rows = [_input_terms(t, b) for t, b in zip(techs, budgets)]
    terms = np.asarray([[r[k] for k in _TERMS] for r in rows],
                       dtype=np.float64).astype(np.float32)
    if n_pad > n:       # repeat a real row: padding stays finite
        terms = np.concatenate([terms, np.repeat(terms[:1], n_pad - n, 0)])
    vals = np.asarray(age_rows(terms))[:n]
    _count(calls=1, rows=n, padded_rows=n_pad)
    return [_microarch(t, dict(zip(_OUTPUTS, v)))
            for t, v in zip(techs, vals)]


def fixed_microarch(tech: TechConfig, *, compute_flops: float, dram_bw: float,
                    dram_capacity: float, net_inter_bw: float,
                    net_inter_links: float = 4.0,
                    net_intra_bw: Optional[float] = None,
                    l2_bytes: float = 128 * 2**20, l2_bw: Optional[float] = None,
                    l1_bytes: float = 128 * 2**20, l1_bw: Optional[float] = None,
                    l0_bytes: float = 256 * 2**10, l0_bw: Optional[float] = None,
                    ) -> MicroArch:
    """Bypass the AGE with *known* hardware (TPU v5e, CPU host): used when we
    model existing silicon rather than explore hypothetical budgets."""
    l2_bw = l2_bw if l2_bw is not None else dram_bw * 6.0
    l1_bw = l1_bw if l1_bw is not None else dram_bw * 24.0
    l0_bw = l0_bw if l0_bw is not None else compute_flops * 2.0  # regs feed MXU
    return MicroArch(
        tech=tech,
        n_mcu=4.0,
        core_frequency=tech.compute.nominal_frequency,
        compute_throughput=compute_flops * tech.compute.max_utilization,
        mem_capacity=(l0_bytes, l1_bytes, l2_bytes),
        mem_bw=(l0_bw, l1_bw, l2_bw),
        mem_latency=(0.5e-9, 5e-9, 15e-9),
        dram_capacity=dram_capacity,
        dram_bw=dram_bw,
        dram_latency=tech.dram.access_latency_s,
        net_intra_bw=net_intra_bw if net_intra_bw is not None else net_inter_bw,
        net_intra_links=4.0,
        net_intra_latency=tech.net_intra.link_latency_s,
        net_inter_bw=net_inter_bw,
        net_inter_links=net_inter_links,
        net_inter_latency=tech.net_inter.link_latency_s,
    )


def tpu_v5e_microarch() -> MicroArch:
    """The dry-run/roofline target: 197 TF bf16, 819 GB/s HBM, 50 GB/s ICI."""
    return fixed_microarch(
        techlib.tpu_v5e_tech(),
        compute_flops=197e12,
        dram_bw=819e9,
        dram_capacity=16.0 * 2**30,
        net_inter_bw=50e9,
        net_inter_links=4.0,
        l1_bytes=128 * 2**20,           # VMEM
    )


def cpu_host_microarch(compute_flops: float = 5.0e10,
                       dram_bw: float = 1.2e10) -> MicroArch:
    """Calibratable model of THIS container's CPU (validation hardware)."""
    return fixed_microarch(
        techlib.cpu_host_tech(),
        compute_flops=compute_flops,
        dram_bw=dram_bw,
        dram_capacity=16.0 * 2**30,
        net_inter_bw=10e9,
        l2_bytes=32 * 2**20, l2_bw=dram_bw * 6,
        l1_bytes=1 * 2**20, l1_bw=dram_bw * 20,
        l0_bytes=64 * 2**10,
    )
