"""ListOps rows (bench/gen.py), each batch's rows in order of length."""
from bench import gen


def batches(seed, traffic, *, count, vocab_size):
    return gen.listops_batches(seed, count=count, batch=traffic["batch"],
                               seq_len=traffic["seq_len"])
