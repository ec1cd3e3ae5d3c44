"""Operations and bytes of one batch's range COMPLETION, as the
ALGORITHM needs them: the batch's long queries (the traffic's
``heavy_family`` share: the ones whose result list passes the first
pass's k) against every placed row once, 2 x long x rows x dim
operations, each row and each long query read once in float32.  Not the
passes an implementation happens to make (a float32 product made of
several bfloat16 ones, a padded sub-batch), so no change of arm makes
the count stale or pushes a share of the roofline over 100.
"""


def ops_bytes(config: dict, traffic: dict):
    q = int(traffic["shares"]["heavy_family"])
    n, d = int(config["rows_n"]), int(config["dim"])
    return 2.0 * q * n * d, 4.0 * n * d + 4.0 * q * d


def least_seconds(config: dict, traffic: dict, peaks: dict) -> float:
    """The least time one chip could take for one batch's completion:
    the larger of operations over the peak bf16 rate and bytes over the
    peak HBM rate."""
    ops, nbytes = ops_bytes(config, traffic)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
