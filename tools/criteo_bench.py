"""BASELINE config #1 measurement: train->predict->AUC on the 1M-row
Criteo-Kaggle-like sample (data/synth.py), on whatever device is
present (chip_smoke.py is the run that refuses anything but a TPU).

Runs the real CLI end to end, measures wall-clock training throughput
and score-file test AUC, trains the independent NumPy SGD-FM oracle on
the same data, and prints one JSON blob.

Usage: python tools/criteo_bench.py [n_train] [n_test]
       [--seed 17] [--k 8] [--lr 0.05]

``--seed`` regenerates the dataset from a different generative draw and
``--k/--lr`` move the model to a different operating point — both with
the oracle re-trained at MATCHED settings, so parity can be pinned at
more than the single (seed, hyperparameter) pair it was first recorded
at (round-4 review: one matching pair could be a coincidence).
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _write_cli_cfg(path, tmp, train, test, *, vocab, k, lr, epochs,
                   lam, batch_size, mfpe, name, general_extra=""):
    """The ONE CLI config template both parity legs (FM and FFM) fill
    in — a schema change edits one string, not per-leg copies."""
    with open(path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = {vocab}
factor_num = {k}
{general_extra}
model_file = {tmp}/model/{name}
log_file = {tmp}/log/{name}.log

[Train]
train_files = {train}
epoch_num = {epochs}
batch_size = {batch_size}
learning_rate = {lr}
factor_lambda = {lam}
bias_lambda = {lam}
init_value_range = 0.01
loss_type = logistic
max_features_per_example = {mfpe}
bucket_ladder = {mfpe}
shuffle = False

[Predict]
predict_files = {test}
score_path = {tmp}/score
""")


def main(n_train: int = 1_000_000, n_test: int = 100_000,
         seed: int = 17, k: int = None, lr: float = 0.05,
         model: str = "fm", order: int = 2) -> None:
    if order not in (2, 3):
        # fail BEFORE the multi-minute framework leg: the oracle only
        # implements orders 2 and 3
        raise SystemExit(f"--order must be 2 or 3, got {order}")
    if model == "ffm":
        if order != 2:
            raise SystemExit("--model ffm supports order 2 only "
                             "(field-aware FM is pairwise by "
                             "definition); drop --order")
        return main_ffm(n_train, n_test, seed=seed,
                        k=(4 if k is None else k), lr=lr)
    k = 8 if k is None else k
    import run_tffm
    from fast_tffm_tpu.data import synth
    from fast_tffm_tpu.metrics import exact_auc

    vocab = 1 << 22
    epochs, lam = 2, 1e-6
    with tempfile.TemporaryDirectory() as tmp:
        train = os.path.join(tmp, "train.txt")
        test = os.path.join(tmp, "test.txt")
        t0 = time.time()
        meta = synth.write_dataset(train, test, n_train, n_test, seed=seed)
        gen_sec = time.time() - t0

        cfg_path = os.path.join(tmp, "ck.cfg")
        extra = "hash_feature_id = True"
        if order != 2:
            extra += f"\norder = {order}"
        _write_cli_cfg(cfg_path, tmp, train, test, vocab=vocab, k=k,
                       lr=lr, epochs=epochs, lam=lam, batch_size=8192,
                       mfpe=48, name="ck", general_extra=extra)
        t0 = time.time()
        if run_tffm.main(["train", cfg_path]) != 0:
            raise SystemExit("train failed; not recording metrics")
        train_sec = time.time() - t0
        t0 = time.time()
        if run_tffm.main(["predict", cfg_path]) != 0:
            raise SystemExit("predict failed; not recording metrics")
        predict_sec = time.time() - t0

        scores = np.loadtxt(os.path.join(tmp, "score", "test.txt.score"))
        labels = np.loadtxt(test, usecols=0)
        fw_auc = exact_auc(scores, labels)

        # Independent oracle: SAME data, SAME batch size/hyperparameters
        # (a mismatched batch size changes the step count and therefore
        # Adagrad progress — the first run of this tool showed exactly
        # that confound). Minutes of numpy time, once per round.
        t0 = time.time()
        tr = synth.parse_file_blocks(train, vocab, 8192)
        te = synth.parse_file_blocks(test, vocab, 8192)
        oracle_auc = exact_auc(
            synth.numpy_fm_train_predict(tr, te, vocab, k=k, lr=lr,
                                         epochs=epochs, factor_lambda=lam,
                                         bias_lambda=lam, order=order),
            labels)
        oracle_sec = time.time() - t0

    print(json.dumps({
        "config": ("baseline#1 criteo-kaggle-like" if order == 2
                   else "baseline#4 order-3 criteo-kaggle-like"),
        "seed": seed, "k": k, "lr": lr, "order": order,
        "n_train": n_train, "n_test": n_test, "epochs": epochs,
        "gen_sec": round(gen_sec, 1),
        "train_sec": round(train_sec, 1),
        "train_examples_per_sec": round(n_train * epochs / train_sec, 1),
        "predict_sec": round(predict_sec, 1),
        "test_auc": round(fw_auc, 4),
        "oracle_auc": round(oracle_auc, 4),
        "oracle_sec": round(oracle_sec, 1),
        "bayes_auc": round(meta["bayes_auc"], 4),
        "positive_rate": round(meta["positive_rate_test"], 4),
    }))


def main_ffm(n_train: int, n_test: int, seed: int = 17, k: int = 4,
             lr: float = 0.05) -> None:
    """BASELINE config #3's AUC-parity leg: Avazu-like field-aware data
    with a KNOWN field-aware generative model, the real CLI FFM
    train→predict, and the independent NumPy FFM-SGD oracle at matched
    hyperparameters (synth.numpy_ffm_train_predict — hand-derived
    field-aware gradients, no shared model code)."""
    import run_tffm
    from fast_tffm_tpu.data import synth
    from fast_tffm_tpu.metrics import exact_auc

    F = len(synth.FFM_FIELDS)
    vocab = synth.ffm_vocab_size()
    B, epochs, lam = 4096, 2, 1e-6
    with tempfile.TemporaryDirectory() as tmp:
        train = os.path.join(tmp, "train.txt")
        test = os.path.join(tmp, "test.txt")
        t0 = time.time()
        meta = synth.write_ffm_dataset(train, test, n_train, n_test,
                                       seed=seed)
        gen_sec = time.time() - t0

        cfg_path = os.path.join(tmp, "ck_ffm.cfg")
        _write_cli_cfg(cfg_path, tmp, train, test, vocab=vocab, k=k,
                       lr=lr, epochs=epochs, lam=lam, batch_size=B,
                       mfpe=F, name="ckffm",
                       general_extra=("model_type = ffm\n"
                                      f"field_num = {F}"))
        t0 = time.time()
        if run_tffm.main(["train", cfg_path]) != 0:
            raise SystemExit("ffm train failed; not recording metrics")
        train_sec = time.time() - t0
        t0 = time.time()
        if run_tffm.main(["predict", cfg_path]) != 0:
            raise SystemExit("ffm predict failed; not recording metrics")
        predict_sec = time.time() - t0

        scores = np.loadtxt(os.path.join(tmp, "score", "test.txt.score"))
        labels = np.loadtxt(test, usecols=0)
        fw_auc = exact_auc(scores, labels)

        t0 = time.time()
        tr = synth.parse_ffm_file(train, B)
        te = synth.parse_ffm_file(test, B)
        oracle_auc = exact_auc(
            synth.numpy_ffm_train_predict(tr, te, vocab, k=k, lr=lr,
                                          epochs=epochs,
                                          factor_lambda=lam,
                                          bias_lambda=lam),
            labels)
        oracle_sec = time.time() - t0

    print(json.dumps({
        "config": "baseline#3 avazu-like ffm",
        "seed": seed, "k": k, "lr": lr, "field_num": F,
        "n_train": n_train, "n_test": n_test, "epochs": epochs,
        "gen_sec": round(gen_sec, 1),
        "train_sec": round(train_sec, 1),
        "train_examples_per_sec": round(n_train * epochs / train_sec, 1),
        "predict_sec": round(predict_sec, 1),
        "test_auc": round(fw_auc, 4),
        "oracle_auc": round(oracle_auc, 4),
        "oracle_sec": round(oracle_sec, 1),
        "bayes_auc": round(meta["bayes_auc"], 4),
        "positive_rate": round(meta["positive_rate_test"], 4),
    }))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("n_train", type=int, nargs="?", default=1_000_000)
    ap.add_argument("n_test", type=int, nargs="?", default=100_000)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--k", type=int, default=None,
                    help="latent dim (default: 8 for fm, 4 for ffm)")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--model", choices=("fm", "ffm"), default="fm")
    ap.add_argument("--order", type=int, choices=(2, 3), default=2,
                    help="FM interaction order (fm model only)")
    a = ap.parse_args()
    main(a.n_train, a.n_test, seed=a.seed, k=a.k, lr=a.lr,
         model=a.model, order=a.order)
