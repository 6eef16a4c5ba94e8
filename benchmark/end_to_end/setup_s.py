"""Process start to window open: loading, making the weights, warming the
cell's own programs (from the compile cache after a checkout's first run)."""


def read(records):
    return records.setup_s
