"""The wrong-family control: another family's reference put in the
program's place, in float64, compared with the cell's own reference by
the numbers a run compares. It must come out NOT correct, or a program
that computes the other model (second-order FM where the configuration
says order 3) would pass the cell's check.

    python3 -m benchmarks.control_family --workload fm3-train-bags \\
        --family fm_order2 --seeds 1,2,3

``control.main`` and ``control.control_numbers`` unedited: for the call
the name ``reference.ReferenceTrainer`` is rebound, as
``corpus_bags.in_place_of_generate`` rebinds the generator, so that the
run control.py makes "in bfloat16" is the other family's at full
precision. Always at the committed configuration. Host only."""

from __future__ import annotations

import argparse
import contextlib
import sys

from benchmarks import control, corpus_bags, harness, reference


@contextlib.contextmanager
def family_in_place_of_bf16(family: str):
    kept = reference.ReferenceTrainer

    class OtherFamily(kept):
        def __init__(self, model, row_ids, table_rows, quant=None):
            if quant == "bf16":
                model, quant = dict(model, reference_family=family), None
            super().__init__(model, row_ids, table_rows, quant)

    reference.ReferenceTrainer = OtherFamily
    try:
        yield
    finally:
        reference.ReferenceTrainer = kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.control_family")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--family", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    a = ap.parse_args(argv)
    bags = harness.load_cell(a.workload).kind == "train_bags"
    with family_in_place_of_bf16(a.family), \
            (corpus_bags.in_place_of_generate() if bags
             else contextlib.nullcontext()):
        return control.main(["--workload", a.workload, "--seeds", a.seeds])


if __name__ == "__main__":
    sys.exit(main())
