"""Bytes of making one batch's validity bits from one scalar attribute a
row, as the algorithm needs them: the attribute read once by each of the
call's launches (4 bytes a row; a launch is a sub-batch of the call, and
each is a program of its own that cannot keep the last one's rows) and
one bit a (query, row) pair written: L x 4N + QN / 8 bytes.  The
compare itself, one a pair, runs on the vector unit and is not counted:
a share under 100 is then what the compares and the packing cost over
the bytes.
"""

#: launches a default call of 4,096 queries or more is cut into
#: (the program's analysis.subbatch.SUB_BATCHES; a call of fewer is one)
LAUNCHES = 4


def ops_bytes(config: dict, traffic: dict):
    q, n = int(traffic["batch_rows"]), int(config["rows_n"])
    launches = LAUNCHES if q >= LAUNCHES * 1024 else 1
    return 0.0, launches * 4.0 * n + q * n / 8.0


def least_seconds(config: dict, traffic: dict, peaks: dict) -> float:
    return ops_bytes(config, traffic)[1] / peaks["hbm_bytes_per_s"]
