"""``knn_scan`` for a corpus that is db-sharded over the cell's chips:
the operations and bytes of ONE chip's share of one exact k-NN scan.
Every chip scans ``rows_n // db_shards`` rows against all the batch's
queries, and the trace gives the kernel's time as the mean over the
chips, so the share of the roofline is one chip's work over one chip's
time.  ``knn_scan`` itself counts ``rows_n`` rows for one chip: at four
shards it would read four times too high.  At one shard the two agree.
"""


def ops_bytes(config: dict, traffic: dict):
    q, d = int(traffic["batch_rows"]), int(config["dim"])
    n = int(config["rows_n"]) // int(config.get("db_shards", 1))
    return 2.0 * q * n * d, 4.0 * n * d + 4.0 * q * d


def least_seconds(config: dict, traffic: dict, peaks: dict) -> float:
    """The least time one chip could take for its share of one batch:
    the larger of operations over the peak bf16 rate and bytes over the
    peak HBM rate."""
    ops, nbytes = ops_bytes(config, traffic)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
