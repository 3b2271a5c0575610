"""Differential semantic fuzz — GENERATED, do not edit by hand.

Generated by scripts/gen_semfuzz.py (via `python -m scripts.jlint
--write-manifest`) from scripts/jlint/semantics_manifest.json; jlint
pass 11 fails (JL1103) when this file does not match a fresh render.
Command streams are derived from the extracted argument grammar of
every natively-served command and driven through the full Server twice
(native engine vs forced-Python oracle) with byte-compared replies —
valid, boundary and mutated-invalid commands alike (the engine defers
every error to the oracle, so help text must byte-match too).

The golden corpus (tests/golden/semfuzz_corpus.json) pins the
generation seed, each stream's sha256, and the manifest's own sha256:
editing the manifest without `--write-corpus` fails here in tier-1.
The deep sweep rides `-m soak`.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from scripts import gen_semfuzz  # noqa: E402
from scripts.jlint import pass_semantics  # noqa: E402

GRAMMAR = {
    'GCOUNT GET': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'GCOUNT INC': {'min_argc': 4, 'u64_args': [3], 'opt_u64_args': [], 'validators': []},
    'MAP TREG GET': {'min_argc': 5, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'MAP TREG GETALL': {'min_argc': 4, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'MAP TREG SET': {'min_argc': 7, 'u64_args': [6], 'opt_u64_args': [], 'validators': []},
    'PNCOUNT DEC': {'min_argc': 4, 'u64_args': [3], 'opt_u64_args': [], 'validators': []},
    'PNCOUNT GET': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'PNCOUNT INC': {'min_argc': 4, 'u64_args': [3], 'opt_u64_args': [], 'validators': []},
    'TLOG CUTOFF': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'TLOG GET': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [3], 'validators': []},
    'TLOG INS': {'min_argc': 5, 'u64_args': [4], 'opt_u64_args': [], 'validators': []},
    'TLOG SIZE': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'TREG GET': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'TREG SET': {'min_argc': 5, 'u64_args': [4], 'opt_u64_args': [], 'validators': []},
    'UJSON CLR': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': [{'arg': 'path', 'check': 'utf8_valid'}]},
    'UJSON GET': {'min_argc': 3, 'u64_args': [], 'opt_u64_args': [], 'validators': []},
    'UJSON INS': {'min_argc': 4, 'u64_args': [], 'opt_u64_args': [], 'validators': [{'arg': 'last', 'check': 'ujson_prim_ok'}, {'arg': 'path', 'check': 'utf8_valid'}]},
    'UJSON RM': {'min_argc': 4, 'u64_args': [], 'opt_u64_args': [], 'validators': [{'arg': 'last', 'check': 'ujson_prim_ok'}, {'arg': 'path', 'check': 'utf8_valid'}]},
    'UJSON SET': {'min_argc': 4, 'u64_args': [], 'opt_u64_args': [], 'validators': [{'arg': 'last', 'check': 'ujson_doc_ok'}, {'arg': 'path', 'check': 'utf8_valid'}]},
}

SEED = 1107


def _corpus() -> dict:
    with open(gen_semfuzz.CORPUS_PATH, encoding="utf-8") as f:
        return json.load(f)


def test_semfuzz_corpus_pins_manifest_and_streams():
    corpus = _corpus()
    assert corpus["manifest_sha256"] == pass_semantics.manifest_sha(), (
        "semantics_manifest.json changed without re-recording the fuzz "
        "corpus — run `python -m scripts.jlint --write-corpus`, review "
        "the stream shas, commit"
    )
    assert corpus["seed"] == SEED
    streams = gen_semfuzz.gen_streams(
        GRAMMAR, corpus["seed"], gen_semfuzz.TIER1_STREAMS,
        gen_semfuzz.TIER1_CMDS,
    )
    pinned = corpus["streams"]
    assert len(streams) == len(pinned)
    for s, p in zip(streams, pinned):
        assert len(s) == p["n_cmds"]
        assert gen_semfuzz.stream_sha(s) == p["sha256"], (
            "generated stream diverged from the golden corpus — the "
            "generator or grammar changed; re-record with --write-corpus"
        )


@pytest.mark.parametrize("idx", range(gen_semfuzz.TIER1_STREAMS))
def test_semfuzz_differential_tier1(idx):
    corpus = _corpus()
    streams = gen_semfuzz.gen_streams(
        GRAMMAR, corpus["seed"], gen_semfuzz.TIER1_STREAMS,
        gen_semfuzz.TIER1_CMDS,
    )
    gen_semfuzz.run_stream_differential(streams[idx])


@pytest.mark.soak
@pytest.mark.slow
def test_semfuzz_differential_soak():
    corpus = _corpus()
    streams = gen_semfuzz.gen_streams(
        GRAMMAR, corpus["seed"] + 1, gen_semfuzz.SOAK_STREAMS,
        gen_semfuzz.SOAK_CMDS,
    )
    for stream in streams:
        gen_semfuzz.run_stream_differential(stream, split=7)
