"""The work a kernel call needs, from its shapes alone.

These count what the algorithm has to move, not what an implementation
happens to move: a kernel that reads whole rows to use a few words of
them is charged for the words, so its roofline share shows the waste.
"""
from __future__ import annotations

import re

WORD = 4                       # bytes of an int32 / uint32

# ``kernels/kv_probe.py`` returns (values [N, 128], hit [N, 1]) int32 for
# N padded queries; a trace names its Pallas call by that signature
KV_PROBE_CALL = re.compile(r"\(s32\[(\d+),128\],s32\[\1,1\]\) "
                           r"\[(pallas|kv_probe)\]$")


def kv_probe_bytes(queries: int, ways: int, key_words: int,
                   value_words: int) -> int:
    """HBM bytes ``queries`` set-associative GET probes need: each reads
    its bucket's tags, keys and values (``ways`` of each), takes its
    bucket id, tag and key in, and writes its value and hit flag out."""
    bucket = ways * (1 + key_words + value_words)
    query = 1 + 1 + key_words
    answer = value_words + 1
    return queries * (bucket + query + answer) * WORD
