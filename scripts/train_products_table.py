"""How the train step's matrix products are fed, read from the compiled step.

    python scripts/train_products_table.py                 # one chip, 2 layers
    python scripts/train_products_table.py --chips 4 --layers 8

Compiles the engine's fused train step at Mistral-7B width for a described,
unattached v5e (``scripts/chip_rehearse.py``'s ``compile_train_step``: the
benchmark's two training cells are ``--chips 1 --layers 2`` and ``--chips 4
--layers 8``) and prints, for every fusion that holds a ``convolution`` (a
``dot_general`` on the TPU), the fusion's name as a device trace shows it,
the product's scope, what each operand's producer fusion computes ("-": the
operand is a value in memory), what else rides in the fusion (an AdamW
update reads ``sqrt``), and the compiler's ``estimated_cycles`` over the
product's own at the MXU's rate — 1.00 is the product alone. PR 44 found six
products a layer at 1.4-2.3 because their ``[4096, 14336]`` operand was a
producer holding an ``exponential`` and a ``divide``.

Runs no chip and no cell runs it; nothing printed here is a measurement
(17 s for one chip, a minute for four).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import chip_rehearse  # noqa: E402
from chip_smoke import log  # noqa: E402
from deepspeed_tpu.profiling.compiled_products import (  # noqa: E402
    V5E_FLOPS_PER_CYCLE, fed_through, product_fusions)


def scope_tail(scope: str) -> str:
    """``bwd layers_1/mlp/down_proj`` of a product's ``op_name``."""
    parts = [p for p in scope.split("/") if p != "dot_general"]
    side = "bwd" if "transpose(jvp(" in scope else "fwd"
    start = next((i for i, p in enumerate(parts)
                  if p.startswith("layers_") or p == "loss_head"),
                 max(len(parts) - 3, 0))
    return f"{side} {'/'.join(parts[start:start + 4])}"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--all", action="store_true",
                    help="also the products under 0.1 TFLOP")
    args = ap.parse_args()
    compiled = chip_rehearse.compile_train_step(
        chip_rehearse.described_devices(), args.layers, args.chips, None,
        f"train_step[{args.chips} chip(s), depth {args.layers}]")
    fusions = product_fusions(compiled.as_text())
    print(f"{'fusion':40s} {'product':46s} {'TFLOP':>6s} {'Mcycles':>8s} "
          f"{'x MXU':>6s}  producers (lhs | rhs) ; epilogue")
    for f in fusions:
        if f.flops < 1e11 and not args.all:
            continue
        fed = " | ".join(",".join(p) or "-" for p in f.producers)
        print(f"{f.name:40s} {scope_tail(f.scope):46s} {f.flops / 1e12:6.3f} "
              f"{f.estimated_cycles / 1e6:8.2f} {f.ratio:6.2f}  {fed} ; "
              f"{','.join(f.epilogue) or '-'}")
    total = sum(f.estimated_cycles for f in fusions)
    alone = sum(f.flops for f in fusions) / V5E_FLOPS_PER_CYCLE
    log(f"{len(fusions)} product fusions, {total / 1e6:.1f}M estimated cycles "
        f"({total / alone:.2f} x their products alone); "
        f"{len(fed_through(fusions, 'exponential'))} fed through a producer "
        f"that holds an exponential")


if __name__ == "__main__":
    main()
