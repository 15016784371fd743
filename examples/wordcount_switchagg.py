"""The paper's MapReduce word-count over a SwitchAgg aggregation tree.

Eight mapper workers (devices) emit (word, 1) KV pairs with a Zipf-0.99
skew (paper §6.1); the aggregation tree combines them hop by hop through
bounded-memory FPE/BPE nodes.  Reports per-level reduction ratios, traffic
with vs without in-network aggregation, and a packet-level *measured*
job-completion-time (``repro.net.sim``: MTU framing, per-link
serialization, go-back-N loss recovery) against the host-only baseline —
the paper's Fig. 9 / Fig. 10 story end to end.

    PYTHONPATH=src python examples/wordcount_switchagg.py
"""

import dataclasses
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as coll
from repro.core import dataplane, planner, reduction_model as rm, tree as tree_lib
from repro.launch.mesh import make_mesh
from repro.net import sim as netsim


def main():
    n_workers = 8
    pairs_per_worker = 4096
    key_variety = 2048
    mesh = make_mesh((4, 2, 1), ("data", "pod", "model"))

    # --- the controller configures the job (paper §3/§4.1 protocol) -------
    tree = tree_lib.from_mesh(mesh, reduce_axes=("data", "pod"))
    ctl = planner.Controller(combiner_budget_pairs=1024)
    msg = ctl.configure(
        planner.LaunchRequest(job_id=1, n_workers=n_workers,
                              expected_pairs=pairs_per_worker,
                              key_variety=key_variety), tree)
    print(f"aggregation tree: {tree.describe()}")
    print(f"controller config: fpe_capacity={msg.fpe_capacity} pairs/node, "
          f"fanins={msg.fanins}")
    pred = rm.reduction_ratio(n_workers * pairs_per_worker, key_variety,
                              msg.fpe_capacity)
    print(f"Eq.(3) predicted reduction at root: {pred:.3f}")

    # --- mappers emit Zipf word streams -----------------------------------
    keys = rm.zipf_keys(n_workers * pairs_per_worker, key_variety,
                        skew=0.99, seed=0).astype(np.int32)
    vals = np.ones_like(keys, dtype=np.float32)
    spec = NamedSharding(mesh, P(("data", "pod")))
    agg = coll.make_kv_tree_aggregator(
        mesh, ("data", "pod"), fpe_capacity=msg.fpe_capacity, ways=4, bpe=True)
    res = agg(jax.device_put(jnp.asarray(keys), spec),
              jax.device_put(jnp.asarray(vals), spec))

    li, lo = np.asarray(res.level_in), np.asarray(res.level_out)
    print("\nper-hop traffic (pairs):")
    total_in = n_workers * pairs_per_worker
    for i, (ax, fin) in enumerate(zip(tree.axes, msg.fanins)):
        print(f"  level {i} ({ax:5s} x{fin}): in={li[i]:6d} out={lo[i]:6d} "
              f"reduction={1 - lo[i]/max(li[i],1):.3f}")
    root_red = 1 - lo[-1] / total_in
    print(f"end-to-end reduction: {root_red:.3f} (predicted {pred:.3f})")

    # verify against exact ground truth
    got = {}
    for k, v in zip(np.asarray(res.keys).tolist(), np.asarray(res.values).tolist()):
        if k != -1:
            got[k] = got.get(k, 0.0) + v
    want = np.bincount(keys, minlength=key_variety)
    ok = all(abs(got.get(k, 0.0) - c) < 1e-3 for k, c in enumerate(want) if c)
    print(f"word counts exact: {ok}")

    # --- measured JCT with vs without in-network aggregation (Fig. 10) ----
    # The packet-level simulator streams the same mapper output through the
    # tree: MTU-framed packets, 10 GbE links (the paper's testbed), line-rate
    # switch processing, and the reducer in-link as the host-only bottleneck.
    print("\nsimulated job-completion-time (packet-level, 10 GbE):")
    cascade = dataplane.plan_from_configure(msg)
    net_cfg = netsim.NetConfig(link_gbps=(netsim.TEN_GBE,) * len(msg.fanins),
                               reducer_gbps=netsim.TEN_GBE)
    jct = netsim.jct_comparison(keys, vals, fanins=msg.fanins, plan=cascade,
                                cfg=net_cfg, axes=tree.axes)
    sw, host = jct["switchagg"], jct["host_only"]
    print(f"  host-only: JCT {jct['jct_host_only_s']*1e3:8.3f} ms  "
          f"({host['arrived_records']} records over the reducer in-link)")
    print(f"  switchagg: JCT {jct['jct_switchagg_s']*1e3:8.3f} ms  "
          f"({sw['arrived_records']} records reach the reducer)")
    print(f"  JCT saved: {jct['jct_saved']:.0%}  "
          f"(reducer-traffic cut {jct['reduction']:.0%})")
    print("  per-level wire bytes (switchagg): "
          + ", ".join(f"{ax}={sw['link_bytes'][ax]/1024:.1f}KiB"
                      for ax in (*tree.axes, "reducer")))

    # loss resilience: 1% packet loss, go-back-N recovery, PSN dedupe —
    # the delivered word counts stay exact while JCT pays for retransmits
    lossy_cfg = dataclasses.replace(net_cfg, loss_rate=0.01, seed=7)
    from repro.net import simulate
    lossy = simulate(netsim.JobSpec(keys=keys, values=vals,
                                    fanins=msg.fanins, plan=cascade,
                                    cfg=lossy_cfg, axes=tree.axes))
    still_exact = all(
        abs(lossy.delivered_table().get(k, 0.0) - c) < 1e-3
        for k, c in enumerate(want) if c)
    print(f"\n1% packet loss: JCT {lossy.jct_s*1e3:.3f} ms "
          f"({lossy.retransmissions} retransmits, "
          f"{lossy.packets_dropped} drops), counts exact: {still_exact}")
    ctl.release(1)


if __name__ == "__main__":
    main()
