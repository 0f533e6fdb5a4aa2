"""AOT plan artifacts: a compiled model serialized as a versioned co-design
artifact that survives a process boundary.

The paper's pipeline ends in an :class:`~repro.backend.plan.ExecutionPlan` —
the typed, slot-planned, tile-annotated form a hardware designer reads.  This
module makes that plan (and everything needed to serve it) a *stable file*:

* **Schema** ``repro-plan-v1`` — one JSON document in the style of the
  autotuner's persisted cache (``repro-autotune-v1``): a ``schema`` field up
  front, deterministic key order, atomic writes (tempfile + ``os.replace``,
  same discipline as :class:`repro.core.cache.PersistentJsonStore`).
* **npz sidecar** — the plan's baked constants (padded weight/bias/scale
  arrays, LUTs) are numeric bulk, not structure: they live next to the JSON
  in ``<path stem>.npz``, keyed per step, with a sha256 digest recorded in
  the JSON so a mismatched or truncated sidecar is rejected at load.
* **Warm start** — :func:`save_artifact` records the *hot scenario cells*
  resident in the model's :class:`~repro.backend.plan.PlanCache` (and the
  tile choice + ``heuristic|tuned|cache`` source of every fused step in
  them).  :func:`load_artifact` rebuilds the compiled model **without
  re-running passes, fusion or lowering** — no ``compile.fuse`` /
  ``compile.lower`` span is ever emitted on load — and pre-seeds the plan
  cache by replaying each recorded cell through
  :func:`~repro.backend.lowering.specialize_plan` with a replay tuner that
  stamps the recorded tiles and source tags back in.  Serving the recorded
  traffic mix then specializes nothing new (cache misses stay at zero).
* **Provenance** — the ``PlanProvenance`` record round-trips through the
  artifact.  The loaded *live* record carries the passes/fusions history
  verbatim and re-records the hot cells as it re-seeds them (with their
  original source tags); the artifact JSON itself retains the full
  specialization history, including cells that had already been evicted.

``scripts/plan_diff.py`` renders a structural diff of two artifacts (steps,
tiles, buffer slots) — the hardware-designer workflow for comparing plan
versions without loading either one.
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import tempfile
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from typing import TYPE_CHECKING

from ..core import pqir
from ..kernels import ops as kops
from ..obs.provenance import PlanProvenance
from .lowering import specialize_plan
from .plan import (
    Arg,
    ExecutionPlan,
    PlanStep,
    StateBinding,
    ValueInfo,
    bindings_key,
    resolve_bucketing,
)

if TYPE_CHECKING:  # imported lazily at runtime: core.compile imports this package
    from ..core.compile import CompiledModel

__all__ = ["ARTIFACT_SCHEMA", "save_artifact", "load_artifact", "sidecar_path"]

#: Versioned schema id — load rejects anything else.
ARTIFACT_SCHEMA = "repro-plan-v1"

#: Shape-record tile fields recorded per hot cell (subset present per step).
#: ``bits`` rides along for sub-8-bit weight cells (absent means int8), so a
#: plan_diff of a w4 artifact against its w8 twin surfaces the precision.
#: ``b/s/t/dh/bq`` are the fused-attention record (``bq`` is its tuned tile).
_TILE_KEYS = ("m", "bm", "bk", "bn", "bits", "b", "s", "t", "dh", "bq")


def sidecar_path(path: str) -> str:
    """The npz sidecar belonging to an artifact JSON path (``x.json`` →
    ``x.npz``; extensionless paths just append ``.npz``)."""
    stem, ext = os.path.splitext(path)
    return (stem if ext else path) + ".npz"


# ---------------------------------------------------------------------------
# params encoding: JSON with typed markers for the non-JSON leaves
# ---------------------------------------------------------------------------

def _enc(v: Any) -> Any:
    """Encode one params value: tuples and ndarrays get typed markers so the
    decode side restores the exact in-memory form (plan params are compared
    structurally by tests and plan_diff)."""
    if isinstance(v, np.ndarray):
        return {"__ndarray__": pqir._encode_array(v)}
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.bool_,)):
        return bool(v)
    if isinstance(v, tuple):
        return {"__tuple__": [_enc(x) for x in v]}
    if isinstance(v, list):
        return [_enc(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _enc(x) for k, x in v.items()}
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    raise TypeError(f"cannot serialize plan param of type {type(v).__name__}: {v!r}")


def _dec(v: Any) -> Any:
    if isinstance(v, dict):
        if "__ndarray__" in v:
            return pqir._decode_array(v["__ndarray__"])
        if "__tuple__" in v:
            return tuple(_dec(x) for x in v["__tuple__"])
        return {k: _dec(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_dec(x) for x in v]
    return v


def _shape_to_json(shape: Optional[Tuple]) -> Optional[List]:
    # dims may be int, named-axis str, or None (unknown) — all JSON-safe
    return None if shape is None else list(shape)


def _shape_from_json(shape: Optional[List]) -> Optional[Tuple]:
    return None if shape is None else tuple(shape)


# ---------------------------------------------------------------------------
# save
# ---------------------------------------------------------------------------

def _cell_records(cm: "CompiledModel") -> List[Dict[str, Any]]:
    """The hot-cell warm-start records: for every specialization resident in
    the plan cache (least- to most-recently used, so re-seeding preserves
    recency), the axis bindings plus each fused step's bound tiles and their
    provenance source tag."""
    if cm.plan_cache is None:
        return []
    sources = _tile_sources(cm.plan.provenance)
    shared = getattr(cm, "_shared_cache", False)
    own = cm.model.graph.name
    cells = []
    for key in cm.plan_cache.keys():
        bkey = key
        if shared:
            # fleet-shared cache: keys are (graph name, bindings key) — only
            # this model's cells belong in its artifact
            if not (isinstance(key, tuple) and len(key) == 2 and key[0] == own):
                continue
            bkey = key[1]
        entry = cm.plan_cache.peek(key)
        if entry is None:
            continue
        plan, _ = entry
        bindings = dict(bkey)
        if plan.batch == "dynamic":
            # a partially-bound template in the cache cannot be replayed as a
            # warm cell (it has no tiles of its own); skip it
            continue
        tiles: Dict[str, Any] = {}
        for step in plan.steps:
            shape = step.params.get("shape")
            if not isinstance(shape, dict) or not ("bm" in shape or "bq" in shape):
                continue
            name = step.name or step.kernel
            rec = {k: int(shape[k]) for k in _TILE_KEYS if k in shape}
            rec["source"] = sources.get((bkey, name), "heuristic")
            tiles[name] = rec
        cells.append({"bindings": bindings, "tiles": tiles})
    return cells


def _tile_sources(prov: Optional[PlanProvenance]) -> Dict[Tuple, str]:
    """(bindings key, step name) → tile source, parsed from the provenance
    specialization events (the latest event per cell wins — a tuned swap
    re-records the cell with its ``[tuned]`` tag)."""
    out: Dict[Tuple, str] = {}
    if prov is None:
        return out
    for ev in prov.specializations:
        for name, rec in ev.tiles:
            source = "heuristic"
            if rec.endswith("]") and " [" in rec:
                source = rec[rec.rindex(" [") + 2 : -1]
            out[(ev.bindings, name)] = source
    return out


def save_artifact(cm: "CompiledModel", path: str) -> str:
    """Serialize a compiled model (template or static plan, baked consts,
    provenance, hot scenario cells) to ``path`` + its npz sidecar.

    Both files are written atomically (tempfile in the destination directory,
    then ``os.replace``): a crashed save never leaves a half-written
    artifact, and a concurrent reader sees the old version or the new one.
    Returns ``path``.

    Axis bucketing specs must be declarative (``None`` = power-of-two, int =
    round-up granularity) — a custom *callable* policy cannot survive a
    process boundary and is rejected here rather than mis-serialized.
    """
    for axis, spec in cm.axis_specs.items():
        if spec is not None and not isinstance(spec, int):
            raise ValueError(
                f"axis {axis!r} uses a callable bucketing policy, which cannot "
                "be serialized — compile with a declarative spec (None or an "
                "int granularity) to make the model AOT-saveable"
            )
    plan = cm.plan
    arrays: Dict[str, np.ndarray] = {}
    steps_json: List[Dict[str, Any]] = []
    for i, step in enumerate(plan.steps):
        consts_json: List[Optional[Dict[str, Any]]] = []
        for j, c in enumerate(step.consts):
            if c is None:
                consts_json.append(None)
                continue
            key = f"s{i}_c{j}"
            arrays[key] = np.asarray(c)
            consts_json.append({"key": key, "jax": isinstance(c, jax.Array)})
        steps_json.append(
            {
                "kernel": step.kernel,
                "args": [[a.kind, a.index, a.name] for a in step.args],
                "out_slots": list(step.out_slots),
                "params": _enc(step.params),
                "consts": consts_json,
                "kind": step.kind,
                "name": step.name,
                "outputs": list(step.outputs),
                "out_info": [
                    None if info is None else [info.dtype, _shape_to_json(info.shape)]
                    for info in step.out_info
                ],
            }
        )
    doc = {
        "schema": ARTIFACT_SCHEMA,
        "model": cm.model.to_json(),
        "plan": {
            "backend": plan.backend,
            "num_slots": plan.num_slots,
            "inputs": [[n, s] for n, s in plan.inputs],
            "outputs": [[n, s] for n, s in plan.outputs],
            "batch": plan.batch if isinstance(plan.batch, str) else _enc(plan.batch),
            "axes": list(plan.axes),
            "steps": steps_json,
            # persistent state slots (the token path's int8 KV cache): name,
            # tensor endpoints, pinned slots, dtype and (possibly symbolic)
            # shape all round-trip, so a loaded plan still knows which
            # buffers it carries across invocations
            "states": [
                [s.name, s.input, s.output, s.in_slot, s.out_slot,
                 s.dtype, _shape_to_json(s.shape)]
                for s in plan.states
            ],
        },
        "provenance": None if plan.provenance is None else plan.provenance.to_dict(),
        "stats": {k: int(v) for k, v in cm.stats.items()},
        "axis_specs": {a: spec for a, spec in cm.axis_specs.items()},
        "plan_cache_capacity": cm.plan_cache_capacity,
        "cells": _cell_records(cm),
    }
    npz_path = sidecar_path(path)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    payload = buf.getvalue()
    doc["sidecar"] = {
        "file": os.path.basename(npz_path),
        "sha256": hashlib.sha256(payload).hexdigest(),
    }
    _atomic_write(npz_path, payload)
    _atomic_write(
        path, json.dumps(doc, indent=1, sort_keys=True).encode("utf-8")
    )
    return path


def _atomic_write(path: str, payload: bytes) -> None:
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".artifact-", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------

class _ReplayTuner:
    """``tune_step`` provider that replays an artifact's recorded per-cell
    tiles instead of measuring: pre-seeding a loaded plan cache reproduces
    exactly the tiles (and provenance source tags) the saving process served,
    whether they came from the heuristic, a live search or the tuner's own
    persisted cache."""

    def __init__(self, cells: List[Dict[str, Any]]) -> None:
        self._tiles: Dict[Tuple, Dict[str, Any]] = {}
        for cell in cells:
            key = bindings_key({a: int(v) for a, v in cell["bindings"].items()})
            for name, rec in cell.get("tiles", {}).items():
                self._tiles[(key, name)] = rec

    def tune_step(self, step, shape, *, backend: str, bindings: Dict[str, int]):
        rec = self._tiles.get((bindings_key(bindings), step.name or step.kernel))
        if rec is None:
            return shape, "heuristic"
        if "bq" in rec:  # fused attention: the query row-block is the tile
            shape = dict(shape, bq=int(rec["bq"]))
        else:
            shape = kops.with_tiles(
                shape,
                bm=rec.get("bm"),
                bk=rec.get("bk"),
                bn=rec.get("bn"),
            )
        return shape, str(rec.get("source", "heuristic"))


def _load_doc(path: str) -> Dict[str, Any]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise ValueError(f"{path}: not a valid plan artifact (corrupt JSON: {e})")
    if not isinstance(doc, dict) or "schema" not in doc:
        raise ValueError(f"{path}: not a valid plan artifact (no schema field)")
    if doc["schema"] != ARTIFACT_SCHEMA:
        raise ValueError(
            f"{path}: schema {doc['schema']!r} does not match expected "
            f"{ARTIFACT_SCHEMA!r}"
        )
    return doc


def _load_sidecar(path: str, doc: Dict[str, Any]) -> Dict[str, np.ndarray]:
    npz_path = os.path.join(
        os.path.dirname(os.path.abspath(path)), doc["sidecar"]["file"]
    )
    try:
        with open(npz_path, "rb") as f:
            payload = f.read()
    except FileNotFoundError:
        raise ValueError(f"{path}: missing npz sidecar {npz_path}")
    digest = hashlib.sha256(payload).hexdigest()
    if digest != doc["sidecar"]["sha256"]:
        raise ValueError(
            f"{path}: npz sidecar digest mismatch (artifact and sidecar are "
            "from different saves, or the sidecar is corrupt)"
        )
    with np.load(io.BytesIO(payload)) as npz:
        return {k: npz[k] for k in npz.files}


def load_artifact(
    path: str,
    *,
    registry=None,
    autotuner=None,
    plan_cache=None,
    warm: bool = False,
) -> "CompiledModel":
    """Reconstruct a :class:`CompiledModel` from an artifact — **zero
    re-compilation**: no optimization passes run, no fusion patterns match,
    no liveness planning happens (and no ``compile.fuse``/``compile.lower``
    span is emitted).  The plan cache is pre-seeded with every hot cell
    recorded at save time (recorded tiles + source tags replayed through
    :func:`specialize_plan`, so only ``backend.specialize`` spans appear);
    serving the recorded traffic therefore specializes nothing new.

    ``warm=True`` additionally executes each pre-seeded cell once on zero
    feeds, forcing the jit trace/compile up front — a replica warm-started
    this way serves its first real batch at steady-state latency.

    ``registry``/``autotuner``/``plan_cache`` attach exactly as on a fresh
    compile (the tuner only engages for *new* cells beyond the recorded set;
    a shared ``plan_cache`` receives the pre-seeded cells under their
    graph-qualified keys).
    """
    from ..core.compile import CompiledModel

    doc = _load_doc(path)
    arrays = _load_sidecar(path, doc)
    model = pqir.Model.from_json(doc["model"])
    model.validate()
    p = doc["plan"]
    steps = []
    for sj in p["steps"]:
        consts = tuple(
            None
            if cj is None
            else (jax.numpy.asarray(arrays[cj["key"]]) if cj["jax"] else arrays[cj["key"]])
            for cj in sj["consts"]
        )
        steps.append(
            PlanStep(
                kernel=sj["kernel"],
                args=tuple(Arg(k, i, n) for k, i, n in sj["args"]),
                out_slots=tuple(sj["out_slots"]),
                params=_dec(sj["params"]),
                consts=consts,
                kind=sj["kind"],
                name=sj["name"],
                outputs=tuple(sj["outputs"]),
                out_info=tuple(
                    None if ij is None else ValueInfo(ij[0], _shape_from_json(ij[1]))
                    for ij in sj["out_info"]
                ),
            )
        )
    prov = None
    if doc["provenance"] is not None:
        # passes/fusions carry over verbatim; the live record re-accumulates
        # its specialization history as the hot cells are re-seeded below
        # (the artifact JSON keeps the full saved history, evicted cells
        # included)
        pd = dict(doc["provenance"])
        pd["specializations"] = []
        prov = PlanProvenance.from_dict(pd)
    batch = p["batch"] if isinstance(p["batch"], str) else _dec(p["batch"])
    plan = ExecutionPlan(
        backend=p["backend"],
        steps=steps,
        num_slots=int(p["num_slots"]),
        inputs=tuple((n, int(s)) for n, s in p["inputs"]),
        outputs=tuple((n, int(s)) for n, s in p["outputs"]),
        batch=batch,
        axes=tuple(p["axes"]),
        provenance=prov,
        states=tuple(
            StateBinding(
                name=n, input=i, output=o, in_slot=int(isl), out_slot=int(osl),
                dtype=d, shape=_shape_from_json(sh),
            )
            for n, i, o, isl, osl, d, sh in p.get("states", [])
        ),
    )
    axis_specs = {
        a: (None if spec is None else int(spec))
        for a, spec in doc["axis_specs"].items()
    }
    cm = CompiledModel(
        model,
        plan,
        {k: int(v) for k, v in doc["stats"].items()},
        None,
        plan_cache_capacity=int(doc["plan_cache_capacity"]),
        dynamic_axes={a: resolve_bucketing(spec) for a, spec in axis_specs.items()},
        axis_specs=axis_specs,
        autotuner=autotuner,
        plan_cache=plan_cache,
    )
    cells = doc.get("cells", [])
    if cells and cm.plan_cache is not None:
        replay = _ReplayTuner(cells)
        for cell in cells:
            bindings = {a: int(v) for a, v in cell["bindings"].items()}
            spec = specialize_plan(plan, bindings, tuner=replay)
            fn = spec.jit()
            # direct put — no lookup, so hit/miss counters stay untouched and
            # "zero new specializations" is observable as misses == 0; routed
            # through cache_key so a shared (fleet) cache gets the same
            # graph-qualified key the model will look up with
            cm.plan_cache.put(cm.cache_key(bindings), (spec, fn))
            if warm:
                feeds = _zero_feeds(cm, bindings)
                if feeds is not None:
                    fn(feeds)
    if registry is not None:
        cm.attach_metrics(registry)
    return cm


def _zero_feeds(cm: "CompiledModel", bindings: Dict[str, int]):
    """Zero-filled feeds at a cell's bucket extents (jit priming only).
    Returns None when any input dim cannot be resolved to an int."""
    feeds = {}
    for t in cm.model.graph.inputs:
        dims = list(t.shape)
        for axis, by_input in cm.axis_input_pos.items():
            pos = by_input.get(t.name)
            if pos is not None and axis in bindings:
                dims[pos] = bindings[axis]
        if not all(isinstance(d, int) for d in dims):
            return None
        feeds[t.name] = jax.numpy.zeros(tuple(dims), np.dtype(t.dtype))
    return feeds


# ---------------------------------------------------------------------------
# CLI smoke (mirrors repro.backend.autotune's cold/warm discipline for CI)
# ---------------------------------------------------------------------------

def _smoke_model():
    from ..core.toolchain import MLPSpec, quantize_mlp

    rng = np.random.default_rng(11)
    spec = MLPSpec(
        weights=[
            rng.normal(size=(16, 32)).astype(np.float32) * 0.2,
            rng.normal(size=(32, 8)).astype(np.float32) * 0.2,
        ],
        biases=[
            rng.normal(size=(32,)).astype(np.float32) * 0.1,
            rng.normal(size=(8,)).astype(np.float32) * 0.1,
        ],
        activations=["Relu", None],
    )
    calib = rng.normal(size=(64, 16)).astype(np.float32)
    return quantize_mlp(spec, calib, name="aot_smoke")


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    from ..core.compile import compile_model
    from ..obs import trace as _trace

    ap = argparse.ArgumentParser(
        description="AOT artifact smoke: compile+serve+save, or warm-load and "
        "assert zero re-lowering + pre-seeded cache hits"
    )
    ap.add_argument("--smoke", action="store_true", required=True)
    ap.add_argument("--out", default="plan_artifact.json")
    ap.add_argument(
        "--expect-warm",
        action="store_true",
        help="load --out instead of compiling: fail unless no fuse/lower "
        "spans fire and every recorded cell is served without a new "
        "specialization",
    )
    args = ap.parse_args(argv)

    model = _smoke_model()
    rng = np.random.default_rng(12)
    xs = {b: rng.integers(-128, 128, (b, 16)).astype(np.int8) for b in (2, 8)}

    if not args.expect_warm:
        cm = compile_model(model, backend="ref", batch="dynamic")
        inp = cm.input_names[0]
        for x in xs.values():
            cm.run({inp: x})
        save_artifact(cm, args.out)
        print(
            f"saved {args.out} (+ sidecar): "
            f"{len(cm.plan.steps)} steps, {len(cm.plan_cache.keys())} hot cells"
        )
        return 0

    tracer = _trace.install()
    try:
        cm = load_artifact(args.out, warm=True)
        inp = cm.input_names[0]
        outs = [cm.run({inp: x}) for x in xs.values()]
    finally:
        _trace.uninstall()
    # the fresh compile runs outside the tracer: its fuse/lower spans are its
    # own business — the assertion below is about the *load* path only
    fresh = compile_model(_smoke_model(), backend="ref", batch="dynamic")
    for x, got in zip(xs.values(), outs):
        want = fresh.run({fresh.input_names[0]: x})
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    relower = len(tracer.spans("compile.fuse")) + len(tracer.spans("compile.lower"))
    stats = cm.plan_cache.stats
    ok = relower == 0 and stats["misses"] == 0 and stats["hits"] == len(xs)
    print(
        f"warm load: fuse/lower spans={relower} plan-cache hits={stats['hits']} "
        f"misses={stats['misses']} (expected {len(xs)} hits, 0 misses)"
    )
    if not ok:
        print("FAIL: warm start re-lowered or re-specialized")
        return 1
    print("OK: zero re-lowering, all recorded cells served from the pre-seeded cache")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
