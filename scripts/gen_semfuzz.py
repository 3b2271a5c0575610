"""Manifest-generated differential RESP fuzzer (jlint pass 11).

``scripts/jlint/semantics_manifest.json`` records the argument grammar
of every natively-served command. This module turns that grammar into
*executable* coverage, the way pass 8 turns the lattice manifest into
``tests/test_lattice_laws.py``:

* ``gen_streams`` builds deterministic command streams from the
  grammar — valid-by-grammar commands, boundary tokens (u64 extremes,
  leading zeros, empty and binary keys, oversized values), and mutated
  invalid commands (arity off by one, corrupted subcommand case,
  non-digit amounts, invalid UTF-8 path components, broken JSON) —
  seeded by ``random.Random`` only, so a (seed, grammar) pair always
  produces the same bytes;
* ``render_harness`` emits ``tests/test_semantic_fuzz.py`` (regenerated
  by ``python -m scripts.jlint --write-manifest``; staleness is JL1103)
  which drives every stream through the full Server twice — native
  engine vs forced-Python oracle — and byte-compares the replies;
* ``write_corpus`` records ``tests/golden/semfuzz_corpus.json``: the
  generation seed, each stream's sha256, and the sha256 of the manifest
  itself — so editing the manifest without re-recording
  (``--write-corpus``) fails in tier-1, golden-corpus-style.

The differential needs no expected-reply model: an invalid command is
help text on BOTH paths (the engine defers every error to the oracle),
so byte-equality is the whole assertion.
"""

from __future__ import annotations

import hashlib
import json
import os
import random

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS_PATH = os.path.join(ROOT, "tests", "golden", "semfuzz_corpus.json")

# deterministic token pools (binary-safety rides on the RESP array
# framing: keys and values may contain \r\n, NUL, and invalid UTF-8)
KEYS = [b"k", b"key2", b"", b"a b", b"caf\xc3\xa9", b"\x00\xff\r\n", b"x" * 300]
U64_VALID = [b"0", b"1", b"7", b"007", b"1000000007", b"18446744073709551615"]
U64_INVALID = [b"", b"-1", b"+2", b"9" * 25, b"1x", b"0x10", b" 1", b"zz"]
PRIMS = [b"1", b"-2.5", b"true", b"false", b"null", b'"s"', b'"caf\xc3\xa9"']
DOCS = PRIMS + [b'{"a":1}', b"[1,2,3]", b'{"a":{"b":[1]}}']
BAD_JSON = [b"{", b"nope", b"'x'", b"\xff", b"1 2"]
PATH_PARTS = [b"a", b"tags", b"meta", b"caf\xc3\xa9", b"deep"]
BAD_PATH = b"\xff\xfe"  # invalid UTF-8: native defers, oracle decodes


def _is_path_command(g: dict) -> bool:
    return any(v.get("arg") == "path" for v in g.get("validators", [])) or g.get(
        "kind"
    ) == "path"


def _value_pool(g: dict) -> list[bytes] | None:
    for v in g.get("validators", []):
        if v.get("check") == "ujson_doc_ok":
            return DOCS
        if v.get("check") == "ujson_prim_ok":
            return PRIMS
    return None


def _gen_args(rng: random.Random, key: str, g: dict) -> list[bytes]:
    """One client command (list of RESP array args) for grammar entry
    ``g`` — mostly valid, sometimes boundary, sometimes mutated."""
    # "TYPE SUB", or a composed type's "MAP TREG SUB": the command's
    # fixed words, its arguments behind them
    words = key.split(" ")
    tword, sub = words[0], words[-1]
    first = len(words)  # the key argument's index
    roll = rng.random()
    min_argc = g["min_argc"]
    u64_at = set(g["u64_args"])
    opt_at = set(g["opt_u64_args"])
    values = _value_pool(g)
    pathy = tword == "UJSON"
    argc = min_argc
    if opt_at and rng.random() < 0.5:
        argc = max(argc, max(opt_at) + 1)
    if pathy and rng.random() < 0.6:
        argc += rng.randrange(1, 3)  # deeper paths stay valid-by-grammar
    args = [w.encode() for w in words]
    for i in range(first, argc):
        if i in u64_at or i in opt_at:
            args.append(rng.choice(U64_VALID))
        elif i == first:
            args.append(rng.choice(KEYS))
        elif values is not None and i == argc - 1:
            args.append(rng.choice(values))
        elif pathy:
            args.append(rng.choice(PATH_PARTS))
        else:
            args.append(rng.choice(KEYS))
    if roll < 0.70:
        return args
    if roll < 0.85:  # boundary: extremes in place of the friendly pools
        for i in range(first, len(args)):
            if i in u64_at or i in opt_at:
                args[i] = rng.choice(
                    [b"0", b"18446744073709551615", b"007"]
                )
            elif i == first:
                args[i] = rng.choice([b"", b"x" * 300, b"\x00\xff\r\n"])
        return args
    # mutated-invalid: both paths must converge on the same help text
    mutation = rng.randrange(5)
    if mutation == 0 and len(args) > first:
        args.pop()  # arity short of the grammar
    elif mutation == 1:
        args.append(b"junk")  # extra arg (legal only for path commands)
    elif mutation == 2:
        args[first - 1] = rng.choice(
            [sub.lower().encode(), sub.encode() + b"X"]
        )
    elif mutation == 3 and (u64_at or opt_at):
        idx = rng.choice(sorted(u64_at | opt_at))
        if idx < len(args):
            args[idx] = rng.choice(U64_INVALID)
    elif mutation == 4:
        if values is not None and len(args) > first:
            args[-1] = rng.choice(BAD_JSON)
        elif pathy:
            args.append(BAD_PATH)
        else:
            args[first - 1] = b"NOPE"
    return args


def gen_streams(
    grammar: dict[str, dict], seed: int, n_streams: int, cmds_per_stream: int
) -> list[list[list[bytes]]]:
    """Deterministic [stream][command][arg] bytes from the grammar."""
    items = sorted(grammar.items())
    streams = []
    for s in range(n_streams):
        rng = random.Random((seed << 16) + s)
        stream = []
        for _ in range(cmds_per_stream):
            key, g = items[rng.randrange(len(items))]
            stream.append(_gen_args(rng, key, g))
        streams.append(stream)
    return streams


def encode_stream(stream: list[list[bytes]]) -> bytes:
    """RESP-array wire encoding of a command stream."""
    out = bytearray()
    for args in stream:
        out += b"*%d\r\n" % len(args)
        for a in args:
            out += b"$%d\r\n%s\r\n" % (len(a), a)
    return bytes(out)


def stream_sha(stream: list[list[bytes]]) -> str:
    return hashlib.sha256(encode_stream(stream)).hexdigest()


def grammar_from_manifest(manifest: dict) -> dict[str, dict]:
    """The generation-relevant native grammar, baked into the harness."""
    out: dict[str, dict] = {}
    for key, rec in manifest["commands"].items():
        nat = rec["native"]
        out[key] = {
            "min_argc": nat["min_argc"],
            "u64_args": nat["u64_args"],
            "opt_u64_args": nat["opt_u64_args"],
            "validators": nat["validators"],
        }
    return out


def run_stream_differential(stream: list[list[bytes]], split: int = 3) -> None:
    """Drive one stream through the full Server twice — native engine
    vs forced-Python — and assert byte-identical replies. The client
    half-closes after sending, so the server's read loop drains every
    buffered command, flushes, and closes: read-to-EOF is the complete
    reply stream with no timeouts."""
    import asyncio

    wire = encode_stream(stream)
    cuts = sorted(
        {1 + (len(wire) * i) // (split + 1) for i in range(1, split + 1)}
    )
    packets = [wire[a:b] for a, b in zip([0] + cuts, cuts + [len(wire)])]

    async def run_one(force_python: bool) -> bytes:
        from jylis_tpu.models.database import Database
        from jylis_tpu.server.server import Server
        from jylis_tpu.utils.config import Config
        from jylis_tpu.utils.log import Log

        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        # jlint: blocking-ok — differential-fuzz harness: the one-off
        # server boot may touch the native loader's listdir, and this
        # throwaway loop runs nothing else concurrently
        db = Database(identity=1, engine="python" if force_python else "auto")
        server = Server(cfg, db)
        await server.start()
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.port
            )
            for p in packets:
                writer.write(p)
                await writer.drain()
            writer.write_eof()
            out = b""
            while True:
                chunk = await reader.read(1 << 20)
                if not chunk:
                    break
                out += chunk
            writer.close()
            return out
        finally:
            await server.dispose()

    native = asyncio.run(run_one(False))
    oracle = asyncio.run(run_one(True))
    assert native == oracle, (
        f"semantic divergence (stream sha {stream_sha(stream)[:12]}): "
        f"native reply bytes != oracle reply bytes\n"
        f"native: {native[:400]!r}\noracle: {oracle[:400]!r}"
    )


# tier-1 budget: tiny but real; the deep sweep rides -m soak
TIER1_STREAMS = 3
TIER1_CMDS = 60
SOAK_STREAMS = 25
SOAK_CMDS = 200
DEFAULT_SEED = 1107


def write_corpus(manifest: dict, manifest_sha256: str,
                 path: str = CORPUS_PATH, seed: int = DEFAULT_SEED) -> dict:
    grammar = grammar_from_manifest(manifest)
    streams = gen_streams(grammar, seed, TIER1_STREAMS, TIER1_CMDS)
    corpus = {
        "_comment": (
            "Golden semantic-fuzz corpus — regenerate with `python -m "
            "scripts.jlint --write-corpus` after any semantics_manifest "
            "change (tests/test_semantic_fuzz.py fails on a manifest "
            "edit that was not re-recorded). Streams are derived from "
            "the manifest grammar with random.Random; shas pin both the "
            "generator and the grammar."
        ),
        "manifest_sha256": manifest_sha256,
        "seed": seed,
        "streams": [
            {"sha256": stream_sha(s), "n_cmds": len(s)} for s in streams
        ],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(corpus, f, indent=2, sort_keys=True)
        f.write("\n")
    return corpus


_HARNESS_TEMPLATE = '''\
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

GRAMMAR = {grammar}

SEED = {seed}


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
'''


def render_harness(manifest: dict) -> str:
    grammar = grammar_from_manifest(manifest)
    lines = ["{"]
    for key in sorted(grammar):
        g = grammar[key]
        lines.append(
            f"    {key!r}: {{'min_argc': {g['min_argc']}, "
            f"'u64_args': {g['u64_args']}, "
            f"'opt_u64_args': {g['opt_u64_args']}, "
            f"'validators': {g['validators']}}},"
        )
    lines.append("}")
    return _HARNESS_TEMPLATE.format(
        grammar="\n".join(lines), seed=DEFAULT_SEED
    )
