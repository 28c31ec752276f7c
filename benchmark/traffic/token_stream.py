"""Traffic kind ``token_stream``: a trainer's input, as parameters.

The tokens themselves are made on the device by the program's own
``datasets.TokenStream`` (one ``lax.scan`` chunk generates its batches
in-program, so the host feeds nothing). That class bakes its ``seed``
into the compiled chunk as a constant, so a seed per run would compile
a new program per run; the mix therefore fixes ``stream_seed`` and the
run's ``--seed`` picks WHERE in that one endless stream the run starts
(``start_step``, which enters the program as data: the train state's
step counter). Same seed, same batches; another seed, other batches of
the same shape.
"""
import numpy as np


def generate(params, seed, vocab_size, seconds=None):
    rng = np.random.default_rng(seed)
    start_step = int(rng.integers(1, 2**30))
    batch, seq = params["batch_per_data_shard"], params["seq_len"]
    # One batch for the correctness check, outside the stream (the
    # reference may not depend on the program's generator).
    tokens = rng.integers(0, vocab_size, size=(batch, seq + 1), dtype=np.int32)
    return {
        "stream_seed": int(params["stream_seed"]),
        "start_step": start_step,
        "batch_per_data_shard": batch,
        "seq_len": seq,
        "check_inputs": tokens[:, :-1],
        "check_targets": tokens[:, 1:],
    }
