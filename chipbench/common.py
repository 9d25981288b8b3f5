"""What every cell shares: the manifest, seeds, weights, devices and the result.

Everything here is found by name from ``BENCHMARK.json``: a cell names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``), the configuration names its architecture family
(``reference/<reference>.py``), the mix names its kind (``kinds/<kind>.py``),
each per-layer metric has a reader (``metrics/<name>.py``) and each cell its
limits (``limits/<cell>.json``). Adding any of these adds files; no code here
changes.
"""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import os
import sys
import zlib
from typing import Any, Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
SRC = os.path.join(CHECKOUT, "src")

# the traced part of a --trace 1 window: whole work units until this long
TRACE_SECONDS = 3.0


class BenchError(Exception):
    """The benchmark cannot run here: wrong device, missing or bad file."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration, traffic mix, limits) of a workload, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = load_json(os.path.join(CHECKOUT, conf_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    limits = load_json(os.path.join(HERE, "limits", workload + ".json"))
    return cell, conf, mix, limits


def load_module(kind: str, name: str):
    """chipbench/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def family(name: str):
    """``reference/<name>.py``, loaded once: an architecture family's plain
    reference and what the benchmark knows of the family besides, namely

    - ``program_keys(cfg)``: the configuration file's keys as the program's
      ModelConfig states them, ``reference`` among them (the family's name
      where the program's architecture is the family's);
    - ``WEIGHT_SCALES``: each weight's scale by the end of its name (see
      ``leaf_value``);
    - the operations and bytes that the work needs (see ``flops.py``)."""
    return load_module("reference", name)


def use_program() -> None:
    """Put the program's sources on the import path."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def enable_compile_cache() -> str:
    """JAX's persistent cache: $JAX_COMPILATION_CACHE_DIR, else a fixed
    directory in the checkout. Every program is cached, however fast it
    compiled, so that a second run compiles nothing."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int) -> list:
    """The cell's chips, or BenchError: never a fallback to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise BenchError(f"needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# seeds and weights
# ---------------------------------------------------------------------------

def base_key(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits kept)."""
    import jax
    if seed < 0:
        raise BenchError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed % (1 << 32))
    return jax.random.fold_in(key, seed >> 32)


# a weight table's rule for a norm's scale: values around 1, so the norms
# count
NORM = "norm"


def leaf_value(key, name: str, shape: tuple[int, ...], family_name: str):
    """One weight of one layer, fp32, from its key, by the rule that the
    family's ``WEIGHT_SCALES`` gives the longest end of its name ('moe/w1'
    before 'w1'): a tuple of the axes of ``shape`` that are its fan-in, for
    unit-variance outputs (normal over the fan-in), or ``NORM``."""
    import jax
    import jax.numpy as jnp
    table = family(family_name).WEIGHT_SCALES
    parts = name.split("/")
    ends = ("/".join(parts[i:]) for i in range(len(parts)))
    rule = next((table[e] for e in ends if e in table), None)
    if rule is None:
        # a bias, say: the configuration files state none, and the
        # reference has none
        raise BenchError(f"weight {name!r} is not one the {family_name} "
                         f"reference has")
    z = jax.random.normal(key, shape, jnp.float32)
    if rule == NORM:
        return 1.0 + 0.1 * z
    fan_in = 1
    for axis in rule:
        fan_in *= shape[axis]
    return z * fan_in ** -0.5


def leaf_key(seed_key, name: str, layer: int):
    import jax
    return jax.random.fold_in(
        jax.random.fold_in(seed_key, zlib.crc32(name.encode())), layer)


def leaf_name(path) -> str:
    """'segments/attn/wq' for params['segments'][0][0]['attn']['wq']."""
    import jax
    return "/".join(str(p.key) for p in path
                    if isinstance(p, jax.tree_util.DictKey))


def is_stacked(name: str) -> bool:
    """Leaves under 'segments' carry the layer as their leading dimension."""
    return name.startswith("segments/")


def stacked_layers(flat: list) -> list:
    """For each leaf of a flattened tree (``tree_flatten_with_path``), the
    global layer of each entry along its leading dimension, or None for a
    leaf outside 'segments'.

    The program keeps its layers as segments (``params['segments'][s]``),
    each a pattern of layers (``[s][p]``) whose leaves stack its repeats:
    entry r of place p in segment s is layer first(s) + r * period(s) + p,
    where a segment's period is its number of places and its first layer
    follows the layers of the segments before it. A tree with no such
    indices is one segment of period 1."""
    import jax
    places, segs = [], {}
    for path, s in flat:
        if not is_stacked(leaf_name(path)):
            places.append(None)
            continue
        seg, place = ([p.idx for p in path
                       if isinstance(p, jax.tree_util.SequenceKey)]
                      + [0, 0])[:2]
        period, repeat = segs.get(seg, (0, s.shape[0]))
        segs[seg] = (max(period, place + 1), repeat)
        places.append((seg, place))
    first, n = {}, 0
    for seg in sorted(segs):
        first[seg] = n
        n += segs[seg][0] * segs[seg][1]
    return [None if pl is None else
            [first[pl[0]] + r * segs[pl[0]][0] + pl[1]
             for r in range(segs[pl[0]][1])]
            for pl in places]


def make_weights(seed: int, conf: dict, abstract: Any,
                 shardings: Any = None) -> Any:
    """The weights of a tree of ShapeDtypeStructs, made on the device in
    one jitted call from the seed, scaled by the configuration's family,
    placed by ``shardings``. Leaf values depend only on (seed, leaf name,
    global layer), so a reference that makes one layer at a time gets the
    same numbers."""
    import jax
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [leaf_name(p) for p, _ in flat]
    layers = stacked_layers(flat)
    pairs = [(n, i) for n, ls in zip(names, layers) for i in (ls or [0])]
    if len(set(pairs)) != len(pairs):
        raise BenchError("a weight's (name, layer) repeats: the reference "
                         "draws each layer's weight once")
    family_name = conf["reference"]

    def build(seed_key):
        leaves = []
        for name, ls, (_, s) in zip(names, layers, flat):
            if ls is not None:
                def one(layer, _name=name, _shape=s.shape[1:]):
                    return leaf_value(leaf_key(seed_key, _name, layer),
                                      _name, _shape, family_name)
                v = jax.vmap(one)(jnp.asarray(ls, jnp.int32))
            else:
                v = leaf_value(leaf_key(seed_key, name, 0), name, s.shape,
                               family_name)
            leaves.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(base_key(seed))


def program_weights(seed: int, conf: dict) -> Callable:
    """A stand-in for the program's ``Model.init``: the benchmark's weights
    from ``seed``, on the shardings the program's own rules choose."""
    use_program()
    from repro.sharding import tree_shardings

    def init(model, _key=None):
        specs = model.specs()
        shardings = (tree_shardings(model.rules, specs)
                     if model.rules is not None else None)
        return make_weights(seed, conf, model.abstract(), shardings)

    return init


@contextlib.contextmanager
def seeded_init(seed: int, conf: dict):
    """While the block runs, the program's ``Model.init`` makes the
    benchmark's weights from ``seed`` (its own key is ignored); after it,
    ``Model.init`` is the program's own again."""
    use_program()
    from repro.models import Model
    own, init = Model.init, program_weights(seed, conf)
    Model.init = lambda self, key: init(self)
    try:
        yield
    finally:
        Model.init = own


# ---------------------------------------------------------------------------
# the program's configuration against the file
# ---------------------------------------------------------------------------

def program_config(conf: dict):
    """The program's registered ModelConfig for this file."""
    use_program()
    from repro.config import get_arch
    return get_arch(conf["arch"])


def program_embedding_scale(d_model: int) -> float:
    """What the program's embedding multiplies a looked-up row by."""
    use_program()
    import jax.numpy as jnp
    from repro.models import layers
    row = layers.embed({"tok": jnp.ones((1, d_model), jnp.float32)},
                       jnp.zeros((1,), jnp.int32), jnp.float32, d_model)
    return float(row[0, 0])


def check_program_config(cfg, conf: dict) -> None:
    """The program runs the configuration the file states, or BenchError.

    The file's family (its ``reference``) says which keys are compared,
    ``reference`` itself among them, so that the program's architecture is
    the family's. Each key under the file's ``departures`` (where what it
    states, and the program runs, differs from the source) has to be one
    the family reads."""
    got = family(conf["reference"]).program_keys(cfg)
    bad = {k: (v, conf.get(k)) for k, v in got.items()
           if k not in conf or conf[k] != v}
    unchecked = set(conf.get("departures", {})) - set(got)
    if unchecked:
        bad["departures"] = (sorted(unchecked), "keys this check reads")
    if cfg.padded_vocab != cfg.vocab_size:
        bad["padded_vocab"] = (cfg.padded_vocab, cfg.vocab_size)
    if bad:
        raise BenchError(f"{conf['arch']}: the program's configuration "
                         f"differs from the file (program, file): {bad}")


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def device_info(devices: list) -> dict:
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: Optional[dict] = None) -> str:
    """The last line of standard output; ``checks`` (each compared number
    beside its limit) comes last."""
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading against its limit (a reading is correct at or under
    it; a missing or non-finite reading is not)."""
    import math
    checks, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
