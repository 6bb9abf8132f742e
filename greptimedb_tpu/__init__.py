"""greptimedb_tpu: a TPU-native observability database framework.

A from-scratch, TPU-first re-design of the capabilities of GreptimeDB
(reference surveyed in SURVEY.md): SQL + PromQL over metrics/logs/traces,
Parquet-backed region storage, and a disaggregated frontend/datanode/
metasrv/flownode architecture — with the query-execution hot path lowered
to XLA computations via JAX/pjit/Pallas instead of CPU Arrow kernels.

Layer map (mirrors SURVEY.md §1, re-based on TPU):

- ``servers``   — protocol surface (HTTP SQL/PromQL, Prometheus API, Influx…)
- ``query``     — SQL parser → logical plan → optimizer → XLA physical exec
- ``promql``    — PromQL parser + range-vector evaluation as device kernels
- ``parallel``  — partition rules → jax.sharding.Mesh; dist planner; collectives
- ``storage``   — region engine: WAL + memtable + Parquet SSTs + manifest
- ``meta``      — kv backend, catalog, procedures, heartbeat, failure detection
- ``flow``      — continuous aggregation (batching mode re-query)
- ``datatypes`` — schema + host RecordBatch ↔ padded device tensors
- ``ops``       — TPU kernel library (segment reduce, windowed agg, sort, topk)
"""

__version__ = "0.1.0"

# int64 timestamps are load-bearing across the whole stack (epoch-ms exceeds
# int32); x64 mode must be on before any array is built. Done here, in
# code, so that it holds however the process was started.
import jax as _jax

_jax.config.update("jax_enable_x64", True)
