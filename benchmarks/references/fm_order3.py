"""FM of order 3: rows ``[v (k) | w]``, the d-way factorization machine
of Rendle (Factorization Machines, ICDM 2010, section V-B) at d = 3, in
the shared-parameter form of Blondel, Fujino, Ueda, Ishihata
(Higher-Order Factorization Machines, NIPS 2016): ONE factor matrix for
the degree-2 and the degree-3 term (Rendle writes one matrix a degree).
A line's cells are l = 1..L with row i_l and value x_l (a word drawn
twice is two cells), z_l = x_l v_{i_l}, and per factor column

    A^t(z) = sum over l1 < ... < lt of z_l1 ... z_lt   (the ANOVA kernel)
    score  = sum_l w_{i_l} x_l + sum_f (A^2(z_.f) + A^3(z_.f))

computed here on power sums (Newton's identities), p_m = sum_l z_l^m:

    A^2 = (p1^2 - p2) / 2            dA^2/dz_l = p1 - z_l
    A^3 = (p1^3 - 3 p1 p2 + 2 p3)/6  dA^3/dz_l = ((p1 - z_l)^2 - (p2 - z_l^2))/2

(dA^3/dz_l is A^2 of the line without cell l.) The program computes the
same score by a recurrence over the L slots (ops/interaction.py
``_anova_terms``); nothing of it, of models/oracle.py or of the tests'
pattern file is imported or copied here."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import quantize, scatter_rows


def row_dim(model: dict) -> int:
    return int(model["factor_num"]) + 1


def scores_and_row_grads(model, P, inv, x, fields, quant=None):
    if int(model["order"]) != 3:
        raise ValueError(
            "benchmarks/references/fm_order3.py is the reference of "
            f"order 3 and the program's configuration says order "
            f"{model['order']}: it is the reference of another model")
    B, L = inv.shape
    U, D = P.shape
    rows = quantize(P, quant)[inv]                    # [B, L, D]
    xq = quantize(x, quant)
    w, v = rows[..., -1], rows[..., :-1]
    z = quantize(v * xq[..., None], quant)            # [B, L, k]
    z2 = quantize(np.square(z), quant)
    p1 = quantize(z.sum(axis=1), quant)               # [B, k]
    p2 = quantize(z2.sum(axis=1), quant)
    p3 = quantize(quantize(z2 * z, quant).sum(axis=1), quant)
    a2 = 0.5 * (np.square(p1) - p2)
    a3 = (p1 ** 3 - 3.0 * p1 * p2 + 2.0 * p3) / 6.0
    score = (w * xq).sum(axis=1) + (a2 + a3).sum(axis=-1)

    def backward(ds):
        rest1 = p1[:, None, :] - z                    # the line less cell l
        rest2 = p2[:, None, :] - z2
        dz = rest1 + 0.5 * (np.square(rest1) - rest2)
        g = np.empty((B, L, D))
        g[..., -1] = ds[:, None] * xq
        g[..., :-1] = ds[:, None, None] * xq[..., None] * dz
        return scatter_rows(inv, g, U)
    return score, backward
