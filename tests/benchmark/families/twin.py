"""A second family, for the tests: the dense block under another name.
It delegates to ``dense`` and marks three calls as its own, so a cell of
it shows whose counts the harness took and what it handed over. Found by
name like a shipped family: ``perfbench_tiny`` puts this directory on
``benchmark.families``' search path."""

from benchmark.families import dense
from benchmark.families.dense import (cache_bytes_per_token,  # noqa: F401
                                      flash_train_floor_s, served_logits,
                                      train_flops_per_token, train_steps,
                                      tree)

BYTES_A_CALL, FLOPS_A_CALL = 7.0, 11.0
#: What the harness handed over, call by call; a test clears it first.
CALLS = {"program_config": [], "decode_needed_bytes": [],
         "forward_flops": []}


def program_config(cfg, max_seq, param_dtype):
    CALLS["program_config"].append((max_seq, param_dtype))
    return dense.program_config(cfg, max_seq, param_dtype)


def decode_needed_bytes(cfg, row_contexts, shared_tokens):
    CALLS["decode_needed_bytes"].append((list(row_contexts), shared_tokens))
    return BYTES_A_CALL


def forward_flops(cfg, n_tokens, contexts):
    CALLS["forward_flops"].append((n_tokens, list(contexts)))
    return FLOPS_A_CALL
