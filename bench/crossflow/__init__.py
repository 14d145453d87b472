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

A configuration whose architecture these defaults cannot state brings the
parts that depend on it as a module of its own: its file
(``bench/configs/<name>.json``) names ``bench/crossflow/<module>.py``
under the key ``"reference"``.  The module may define any of:

- ``ArchConfig``: the class the file's ``arch`` is built into (lists
  become tuples); it keeps what the shared code reads (``param_count``,
  ``is_moe``, ``n_layers`` ...), most simply as a subclass of
  `archconfig.ArchConfig`;
- ``build_graph(cfg, cell)``: the workload graph of one shape cell;
- ``weight_bytes(cfg)``, ``kv_cache_bytes(cfg, kv_len, batch)`` and
  ``kv_shard_degree(cfg, strategy)``: the serving memory model
  (`records.MemoryModel`);
- ``candidate_strategies(cfg, cell, mesh_shape)``: the strategy axis.

What it leaves out is the default of `archconfig`, `lmgraph` and
`records`; AGE, `techlib`, `graph`, `transform`, `placement`, `roofline`,
`simulate`, `traffic` and the record fields stay shared.  It may import
these modules and their helpers (`lmgraph._linear`, `_ffn`, `_moe`), and,
like every module here, nothing of ``repro``, so that the yardstick never
follows the program (`tests/bench/test_bench_reference_modules.py`).
"""
