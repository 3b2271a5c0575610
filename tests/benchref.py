"""The benchmark's plain references and generator for the repo's own
tests, loaded by path (they import nothing of the program, and `tests/`
does not rely on the repo root being importable)."""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel: str):
    path = os.path.join(REPO, "benchmark", rel)
    name = "benchref_" + os.path.splitext(os.path.basename(rel))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen = load("harness/gen.py")
resp = load("harness/resp.py")
TLOG = load("reference/TLOG.py")
UJSON = load("reference/UJSON.py")
MAP = load("reference/MAP.py")

TLOG_RECIPE = {"keys": 24, "entries": 20, "value_bytes": 48, "key_format": "thread%07d",
               "ts_epoch_ms": gen.TS_EPOCH_MS, "ts_shift": gen.TS_SHIFT, "base_days": 30}


def tlog_reference(seed: int, **sizes):
    recipe = dict(TLOG_RECIPE, **sizes)
    return TLOG.Reference(recipe, seed, 1, [], gen.hottest(recipe["keys"], recipe["keys"]),
                          gen.Values(seed))


UJSON_RECIPE = {"keys": 12, "members": 40, "path": "members", "key_format": "doc%07d",
                "id_base": 10**18}


def ujson_reference(seed: int, **sizes):
    recipe = dict(UJSON_RECIPE, **sizes)
    return UJSON.Reference(recipe, seed, 1, [], gen.hottest(recipe["keys"], recipe["keys"]))


MAP_RECIPE = {"keys": 2000, "fields": 10, "value_bytes": 100, "key_format": "user%07d",
              "ts_ceiling": gen.TS_EPOCH_MS << gen.TS_SHIFT}


def map_reference(seed: int, own_rid: int = 1, **sizes):
    recipe = dict(MAP_RECIPE, **sizes)
    return MAP.Reference(recipe, seed, own_rid, [], gen.hottest(recipe["keys"], recipe["keys"]),
                         gen.Values(seed))


class Replies:
    """A `Respond` whose replies come back as the harness's RESP parser
    returns them: what `check.compare` holds against `expected`."""

    def __init__(self):
        from jylis_tpu.server.resp import Respond

        self.parser = resp.Parser()
        self.respond = Respond(self.parser.feed)

    def call(self, repo, *words: bytes):
        repo.apply(self.respond, list(words))
        out = self.parser.pop()
        assert out is not resp.Parser.MORE
        return out
