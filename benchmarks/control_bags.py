"""The bfloat16 control (control.py) of a cell whose corpus has bags of
tokens: ``control.main`` with corpus_bags' generator in
``corpus.generate``'s place for the call, nothing of it copied.

    python3 -m benchmarks.control_bags --workload fm8-train-bags --seeds 1,2,3

``control.main`` looks the limits up under the traffic's ``kind``
(``train_bags``) and drivers/train.py under ``train``: the
configuration's file carries the same limits under both."""

from benchmarks import control, corpus_bags


def main(argv=None) -> int:
    with corpus_bags.in_place_of_generate():
        return control.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
