"""Operations and bytes of one exact FILTERED k-NN scan of a query batch
over the placed rows, as the algorithm needs them: ``work/knn_scan.py``'s
(one pass of the Q x N x D distance product; each row and each query
read once in float32) plus the predicate, one bit a (query, row) pair
read once: Q x N / 8 bytes.  Whatever makes or applies the bits, that
is what a per-query predicate over every row costs to read.
"""


def ops_bytes(config: dict, traffic: dict):
    q, n, d = int(traffic["batch_rows"]), int(config["rows_n"]), int(config["dim"])
    return 2.0 * q * n * d, 4.0 * n * d + 4.0 * q * d + q * n / 8.0


def least_seconds(config: dict, traffic: dict, peaks: dict) -> float:
    """The least time one chip could take for one batch: the larger of
    operations over the peak bf16 rate and bytes over the peak HBM
    rate."""
    ops, nbytes = ops_bytes(config, traffic)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
