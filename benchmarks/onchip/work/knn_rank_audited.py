"""Work of the single-grid KNN predict + rank + audit step per batch.

The algorithm's arithmetic and its compulsory HBM traffic at the
bucket's shapes, float32 throughout (4 bytes), whatever implements it:

  KNN      squared distances |x|^2 - 2 x.y + |y|^2 of B queries to n_db
           rows of width d; inverse-distance weights of k neighbours
           over K prices
  rank     adjusted scores s = u + (1 + eps) λ̂·a over B x m1
  audit    utility and K exposures over the m2 selected slots

Bytes: the λ-database (n_db x d rows, n_db x k_pred prices) read once
per batch, the batch's u (B x m1), a (B x K x m1), X, b and gamma in,
and the page (perm, scores, utility, exposure, compliance, λ̂) out.
Comparisons of the top-k and top-m2 selections are not counted: no
published peak bounds them.
"""

F32 = 4


def count(shape: dict) -> dict:
    B, m1, m2, K = shape["B"], shape["m1"], shape["m2"], shape["K"]
    d, n_db, k, k_pred = shape["d"], shape["n_db"], shape["k"], shape["k_pred"]
    flops = (2 * B * n_db * d + 2 * n_db * d + 2 * B * d + 3 * B * n_db
             + B * k * (2 * K + 3)
             + B * m1 * (2 * K + 1)
             + 2 * B * m2 * (K + 1))
    bytes_ = F32 * (n_db * d + n_db * k_pred
                    + B * m1 + B * K * m1 + B * d + B * K + B * m2
                    + 2 * B * m2 + 2 * B + 2 * B * K)
    return {"flops": flops, "bytes": bytes_}
