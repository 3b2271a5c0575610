"""Tests for the infra layer: Address parsing/hashing, name generation,
config CLI, log dual-sink (reference test analogs: test_address.pony,
test_name_generator.pony)."""

import functools
import glob
import os
import random
import re

import pytest

from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import build_parser, config_from_cli
from jylis_tpu.utils.log import Log
from jylis_tpu.utils.namegen import generate_name


def test_address_roundtrip():
    a = Address.from_string("127.0.0.1:9999:fancy-name")
    assert (a.host, a.port, a.name) == ("127.0.0.1", "9999", "fancy-name")
    assert str(a) == "127.0.0.1:9999:fancy-name"


def test_address_degenerate_inputs():
    # address.pony test pins: "", "::::", partial forms
    assert Address.from_string("") == Address("", "", "")
    assert Address.from_string("h") == Address("h", "", "")
    assert Address.from_string("h:p") == Address("h", "p", "")
    a = Address.from_string("::::")
    assert (a.host, a.port, a.name) == ("", "", "::")


def test_address_hash64_deterministic_and_distinct():
    a = Address.from_string("127.0.0.1:9999:x")
    b = Address.from_string("127.0.0.1:9999:y")
    assert a.hash64() == Address.from_string("127.0.0.1:9999:x").hash64()
    assert a.hash64() != b.hash64()
    assert 0 <= a.hash64() < (1 << 64)


def test_namegen_shape_and_determinism():
    # golden: seeded rng must be stable across runs (determinism pin,
    # mirroring test_name_generator.pony's seeded expectations)
    names = [generate_name(random.Random(100 + i)) for i in range(4)]
    assert names == [generate_name(random.Random(100 + i)) for i in range(4)]
    for n in names:
        adj, noun, hex12 = n.split("-")
        assert len(hex12) == 12
        assert all(c in "0123456789abcdef" for c in hex12)


def test_config_defaults():
    cfg = config_from_cli([])
    assert cfg.port == "6379"
    assert cfg.addr.host == "127.0.0.1"
    assert cfg.addr.port == "9999"
    assert cfg.addr.name != ""  # random name filled in
    assert cfg.heartbeat_time == 10.0
    assert cfg.system_log_trim == 200


def test_config_flags():
    cfg = config_from_cli(
        ["-a", "10.0.0.1:7000:n1", "-p", "6380", "-s", "10.0.0.2:7000:n2 10.0.0.3:7000:n3",
         "-T", "0.5", "--system-log-trim", "50", "-L", "debug"]
    )
    assert cfg.addr == Address("10.0.0.1", "7000", "n1")
    assert cfg.port == "6380"
    assert [str(s) for s in cfg.seed_addrs] == ["10.0.0.2:7000:n2", "10.0.0.3:7000:n3"]
    assert cfg.heartbeat_time == 0.5
    assert cfg.system_log_trim == 50
    assert cfg.log.debug()


def test_config_bad_log_level_exits():
    with pytest.raises(SystemExit):
        config_from_cli(["-L", "nope"])


def test_log_levels_and_dual_sink():
    lines = []

    class FakeOut:
        def write(self, s):
            lines.append(s)

        def flush(self):
            pass

    sys_lines = []
    log = Log("warn", FakeOut())
    log.set_sys(sys_lines.append)
    assert not log.info()
    assert log.warn() and log.w("careful")
    assert log.err() and log.e("bad")
    # idiom: level predicate short-circuits the emit call
    log.info() and log.i("never")
    text = "".join(lines)
    assert "(W) careful" in text and "(E) bad" in text and "never" not in text
    assert sys_lines == ["(W) careful", "(E) bad"]


def test_config_version_flag_exits():
    import jylis_tpu as pkg
    import io
    import contextlib

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        try:
            config_from_cli(["--version"])
            raised = False
        except SystemExit as e:
            raised = True
            assert e.code == 0
    assert raised
    assert pkg.__version__ in out.getvalue()


# ---- the flags: the parser, and what the documents say of it ---------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LONG_FLAGS = sorted(
    opt
    for action in build_parser()._actions
    for opt in action.option_strings
    if opt.startswith("--") and opt != "--help"
)

# the multi-lane mode's four (PR 45): refused by argparse itself, no
# shim that takes and ignores them
REMOVED_FLAGS = {
    "--lanes": "2",
    "--lane-id": "0",
    "--lane-bus": "7001,7002",
    "--lane-bus-heartbeat": "0.25",
}

# flags of OTHER programs that the documents name, with the source that
# defines each (the test holds them to it, so this table cannot rot)
OTHER_PROGRAMS = {
    "--workload": "benchmark/run.py",
    "--seed": "benchmark/run.py",
    "--seconds": "benchmark/run.py",
    "--rehearse": "benchmark/run.py",
    "--write-manifest": "scripts/jlint/__main__.py",
    "--write-corpus": "scripts/jlint/__main__.py",
    "--out": "scripts/jlint/__main__.py",
    "--budget": "scripts/jlint/__main__.py",
    "--replay": "scripts/jmodel/__main__.py",
    "--build-arg": None,  # docker build's own
}

_FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*")


@functools.lru_cache(maxsize=None)
def _read(rel: str) -> str:
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _documents() -> list[str]:
    docs = glob.glob(os.path.join(REPO, "docs", "**", "*.md"), recursive=True)
    return ["README.md"] + sorted(os.path.relpath(p, REPO) for p in docs)


def test_the_parser_defines_28_flags():
    assert len(LONG_FLAGS) == 28, LONG_FLAGS


@pytest.mark.parametrize("flag", sorted(REMOVED_FLAGS))
def test_removed_lane_flag_is_refused_by_the_parser(flag, capsys):
    with pytest.raises(SystemExit) as ei:
        config_from_cli([flag, REMOVED_FLAGS[flag]])
    assert ei.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


@pytest.mark.parametrize("flag", LONG_FLAGS)
def test_flag_is_documented_and_documents_name_only_flags(flag):
    """Each flag of the parser has its line in docs/operations.md, and
    a document that names a flag of the node names one the parser
    takes (`--journal-*` style prefixes must open at least one)."""
    assert _FLAG_RE.findall(_read("docs/operations.md")).count(flag), (
        f"{flag} is not named in docs/operations.md"
    )
    flags = set(LONG_FLAGS) | {"--help"}
    for rel in _documents():
        for tok in set(_FLAG_RE.findall(_read(rel))):
            if tok in flags:
                continue
            if tok.endswith("-"):
                assert any(f.startswith(tok) for f in flags), (rel, tok)
                continue
            assert tok in OTHER_PROGRAMS, (
                f"{rel} names {tok}: not a flag of the node"
            )
            src = OTHER_PROGRAMS[tok]
            assert src is None or f'"{tok}"' in _read(src), (tok, src)
