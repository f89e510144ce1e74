"""The paper's central claim, §2.3 + Table 4: for MoE training, TP-sharded
experts sidestep the expert-imbalance straggler problem that EP suffers.

Runs in two parts:
1. Analytic MFU (the paper's own methodology): TP vs EP at increasing
   expert-imbalance coefficients on GPT-MoE 1.1T.
2. Compiled evidence on 8 virtual devices: the same mixtral forward under
   moe_impl=tp vs moe_impl=ep (with the Appendix-G binary-exchange
   all-to-all) produces identical outputs -- the choice is purely a
   systems/performance decision, exactly as the paper argues.

    PYTHONPATH=src python examples/moe_tp_vs_ep.py
"""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

from repro.core.mfu_sim import Cluster, GPT_MOE_1T, search

ROOT = Path(__file__).resolve().parents[1]


def analytic():
    print("== Table 4 reproduction: GPT-MoE 1.1T on 4096 H100s ==")
    tp = search(GPT_MOE_1T, Cluster(4096), global_batch=1536, eps=(1,),
                imbalance=0.0, vpp=3)
    print(f"TP-sharded experts:        MFU {tp.mfu:.4f} (paper 0.312)")
    for imb, ref in ((0.0, 0.315), (0.1, 0.305), (0.2, 0.298), (0.3, 0.288)):
        ep = search(GPT_MOE_1T, Cluster(4096), global_batch=1536, eps=(8,),
                    imbalance=imb, vpp=3)
        mark = "<- EP wins" if ep.mfu > tp.mfu else "<- TP wins"
        print(f"EP-8, imbalance {imb:.0%}:      MFU {ep.mfu:.4f} "
              f"(paper {ref}) {mark}")


def compiled():
    print("\n== compiled equivalence: tp == ep == binary-exchange ==")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    # the check runs on 8 forced host devices; keep the child off any
    # accelerator, which belongs to one process at a time
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_sharded_checks.py"), "moe"],
        capture_output=True, text=True, env=env, timeout=900)
    print(res.stdout.strip() or res.stderr[-500:])


if __name__ == "__main__":
    analytic()
    compiled()
