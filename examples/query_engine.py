"""Sharded SLA-aware query engine, end to end.

  PYTHONPATH=src python examples/query_engine.py

Builds a bit-packed analytic table, shards it across every available device
(run with XLA_FLAGS=--xla_force_host_platform_device_count=8 for a mesh on
CPU), executes AND/OR/mixed-width plans under deadlines through the EDF
scheduler, then closes the paper's loop: measured scan throughput vs the
analytical model's roofline, and a cluster provisioned from *attained*
(not datasheet) throughput.
"""
import jax

from repro.db import Table
from repro.launch.mesh import make_mesh
from repro.query import Pred, Query, QueryEngine, ShardedTable

print("=" * 70)
print("1. A sharded in-memory analytic table")
print("=" * 70)
table = Table.synthetic("sales", 1 << 20,
                        {"price": 16, "region": 8, "qty": 8}, seed=0)
n_dev = len(jax.devices())
mesh = make_mesh((n_dev,), ("data",))
st = ShardedTable.shard(table, mesh)
print(f"  rows={table.num_rows:,}  packed={table.nbytes/1e6:.1f}MB  "
      f"shards={st.n_shards}  rows/shard={st.rows_per_shard:,}")

print()
print("=" * 70)
print("2. Deadline-batched queries (logical plans -> dispatch kernels)")
print("=" * 70)
engine = QueryEngine(st, est_gbps=0.5)
queries = {
    "cheap & west": Query(Pred("price", "lt", 5000)
                          & Pred("region", "lt", 32),
                          aggregates=("price",)),
    "bulk | luxury": Query(Pred("qty", "ge", 100)
                           | Pred("price", "ge", 30000),
                           aggregates=("price", "qty")),
    "fused single-pred": Query(Pred("qty", "lt", 64), aggregates=("qty",)),
}
t0 = engine.clock()
for name, q in queries.items():
    engine.submit(q, deadline=t0 + 30.0)
for name, res in zip(queries, engine.run()):
    price = res.aggregates[res.query.aggregates[0]]
    print(f"  {name:18s} count={res.count:8,}  sel={res.selectivity:.3f}  "
          f"sum={price['sum']:12,}  lat={res.latency_s*1e3:7.1f}ms  "
          f"met={res.met}")
s = engine.summary()
print(f"  -> attainment={s['sla_attainment']:.2f}  "
      f"p99={s['latency_p99_s']*1e3:.1f}ms  "
      f"scan={s['measured_gbps']:.3f} GB/s")

print()
print("=" * 70)
print("3. Model vs measured (the paper's loop, closed)")
print("=" * 70)
mc = engine.model_check()
print(f"  model roofline ({mc['system']}, {mc['chips']} chips): "
      f"{mc['model_gbps']:.0f} GB/s")
print(f"  measured: {mc['measured_gbps']:.3f} GB/s  "
      f"(x{mc['attained_fraction']:.2e} of model — interpret mode on CPU)")
for sla_ms in (10, 100, 1000):
    adv = engine.provision(sla_s=sla_ms / 1e3)
    d = adv.design
    print(f"  provision @ {sla_ms:5d}ms SLA from measured rate: "
          f"{d.compute_chips:6d} chips  {d.power/1e3:8.1f} kW")
