"""Model step: device time of one prefill-chunk program, mean, ms, in
cells where the chunk-carrying steps set the tail of the gap between
tokens. The same reading as `prefill_chunk_ms.ttft`."""

from bench import spec

_same = spec.metric_reader("prefill_chunk_ms.ttft")


def read(ctx):
    return _same.read(ctx)
