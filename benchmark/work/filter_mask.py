"""Bytes of making one batch's validity bits from a tag index, as the
algorithm needs them: for each query, one bit a row of each of its (at
most two) tags read, and one bit a row written: 3 x Q x N / 8 bytes, no
arithmetic to speak of.  An index that keeps a rare tag as a list reads
less for it; the count is of the dense form, so it bounds the share from
above only where every lookup is dense, and the share reads under that
where lists are walked instead (the walk is time the bytes do not
explain).
"""


def ops_bytes(config: dict, traffic: dict):
    q, n = int(traffic["batch_rows"]), int(config["rows_n"])
    return 0.0, 3.0 * q * n / 8.0


def least_seconds(config: dict, traffic: dict, peaks: dict) -> float:
    return ops_bytes(config, traffic)[1] / peaks["hbm_bytes_per_s"]
