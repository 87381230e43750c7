"""Quickstart: the paper's mechanism in 60 seconds.

1. Run a GCN aggregation kernel through the cycle-level CGRA simulator in
   three memory-system configurations (SPM-only / Cache+SPM / +Runahead).
2. Reconfigure the multi-cache system with Algorithm 1.
3. Run the TPU-side analogue: the runahead gather Pallas kernel.

Usage:  PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.cgra import presets, simulate
from repro.core.cgra.reconfig import reconfigure
from repro.core.cgra.trace import gcn_aggregate
from repro.kernels.gather_runahead import ops as gather_ops


def main():
    print("== 1. CGRA memory-subsystem simulation (GCN aggregate, Cora) ==")
    tr = gcn_aggregate("cora")
    spm = simulate(tr, presets.SPM_ONLY_4K)
    cache = simulate(tr, presets.CACHE_SPM)
    ra = simulate(tr, presets.RUNAHEAD)
    print(f" SPM-only(4K) : {spm.cycles:>9} cycles  util={spm.utilization:.2%}")
    print(f" Cache+SPM    : {cache.cycles:>9} cycles  "
          f"speedup={spm.cycles/cache.cycles:.2f}x  "
          f"L1 hit rate={cache.l1_hit_rate:.1%}")
    print(f" +Runahead    : {ra.cycles:>9} cycles  "
          f"speedup={cache.cycles/ra.cycles:.2f}x  "
          f"coverage={ra.coverage:.0%}  accuracy={ra.prefetch_accuracy:.0%}")

    print("\n== 2. Algorithm-1 cache reconfiguration (8x8 multi-cache) ==")
    res = reconfigure(tr, presets.RECONFIG, window=8192)
    base = simulate(tr, presets.RECONFIG)
    new = simulate(tr, res.config)
    print(f" way allocation: {res.allocations}  line sizes: {res.lines}")
    print(f" cycles {base.cycles} -> {new.cycles} "
          f"({(base.cycles-new.cycles)/base.cycles:+.2%})")

    print("\n== 3. TPU adaptation: runahead gather (Pallas) ==")
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(1024, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 1024, 64), jnp.int32)
    out = gather_ops.gather(table, idx, impl="runahead", depth=4)
    ok = bool((np.asarray(out) == np.asarray(table)[np.asarray(idx)]).all())
    print(f" runahead_gather(depth=4): {out.shape} correct={ok}")


if __name__ == "__main__":
    main()
