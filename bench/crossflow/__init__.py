"""Frozen copy of the per-point CrossFlow performance model.

The benchmark decides ``correct`` by comparing what the program's timed
path produced against this copy: the architecture generation engine
(`age`, `techlib`), the workload graphs (`graph`, `lmgraph`), the
parallelism transform and placement (`parallelism`, `transform`,
`placement`), the hierarchical roofline (`roofline`), the event-driven
simulation (`simulate`), the closed-form traffic model (`traffic`) and the
scalar record paths (`records`).  They were copied from ``repro.core`` and
``repro.configs.base`` when the benchmark was defined and import nothing
of the program, so a later change to the program cannot move the
yardstick.  Only the per-point eager path is used here: no batching,
bucketing, compile-ahead or device-resident fold.
"""
