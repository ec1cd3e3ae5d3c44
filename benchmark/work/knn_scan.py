"""Operations and bytes of one exact k-NN scan of a query batch over the
placed rows, as the ALGORITHM needs them: one pass of the Q x N x D
distance matrix product, and each row and each query read once in
float32.  Not the passes an implementation happens to make (the default
arm splits float32 into three bfloat16 products), so no change of arm
makes the count stale or pushes a share of the roofline over 100.
"""


def ops_bytes(config: dict, traffic: dict):
    q, n, d = int(traffic["batch_rows"]), int(config["rows_n"]), int(config["dim"])
    return 2.0 * q * n * d, 4.0 * n * d + 4.0 * q * d


def least_seconds(config: dict, traffic: dict, peaks: dict) -> float:
    """The least time one chip could take for one batch: the larger of
    operations over the peak bf16 rate and bytes over the peak HBM
    rate."""
    ops, nbytes = ops_bytes(config, traffic)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
