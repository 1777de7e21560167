"""``ring_record_median`` of a count of bytes, in GB (1e9 bytes): the
median over the traced window's program calls of a byte count the
program wrote into its span ring. Same arguments; a program that wrote
no such record returns nothing."""
from benchmarks.readers import ring_record_median


def read(ctx, **args):
    value = ring_record_median.read(ctx, **args)
    return None if value is None else value / 1e9
