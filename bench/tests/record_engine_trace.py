"""Record ``data/traces/small_tpu_engine.xplane.pb`` and its scope map.

    python bench/tests/record_engine_trace.py OUT_DIR

on a TPU, from the root of a checkout.  A reduced paged engine
(llama3.2-1b reduced: 4 layers, d_model 64; 2 slots, pages of 8, the XLA
page walk) serves a few short requests, once to compile and once under
the profiler, with an enabled ``repro.obs.Tracer`` (so its live
``serve.*`` spans are profiler annotations) and the harness's own
annotations around each call.  It writes ``OUT_DIR/<profile dirs>`` and
``OUT_DIR/small_tpu_engine.xplane.pb``, the trace cut to what the
reductions read (``trim``), and ``OUT_DIR/small_tpu_engine.scopes.json``,
the ``repro.obs.op_scopes`` map of the instructions that ran (those
outside every scope left out), and prints one JSON summary line.
"""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: the scopes the serving programs open besides the function blocks
PROGRAM_SCOPES = ("kv_write", "head", "mlp", "sample")


def serve(engine, annotate, prompts):
    from bench.trace_reduce import HOST_SPANS
    from repro.serve import Request

    submit, step, _, book = HOST_SPANS
    with annotate(submit):
        for prompt in prompts:
            engine.submit(Request(prompt, max_new_tokens=4))
    while engine.scheduler.has_work:
        with annotate(step):
            events = engine.step()
        with annotate(book):
            len(events)


def _xplane_pb2():
    """The XPlane protobuf module, loaded from its file in the installed
    TensorFlow package without importing TensorFlow itself."""
    import importlib.util

    pkg = importlib.util.find_spec("tensorflow")
    path = pathlib.Path(pkg.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def trim(src: pathlib.Path, dst: pathlib.Path) -> dict:
    """Write ``src`` to ``dst`` with only what the reductions read: the
    device planes' ``XLA Modules`` and ``XLA Ops`` lines (event times and
    names, an operation's name cut to its instruction name; no stats)
    and, of the host, the harness's and the engine's annotations.  Returns
    {module: instruction names that ran}."""
    from bench.trace_reduce import HOST_SPANS, _SUFFIX, _op_name

    pb = _xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(src.read_bytes())
    out = pb.XSpace()
    ran: dict = {}
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name == "/host:CPU"):
            continue
        keep = pb.XPlane(id=plane.id, name=plane.name)
        for sid, sm in plane.stat_metadata.items():
            keep.stat_metadata[sid].CopyFrom(sm)
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            events = [
                e for e in line.events
                if device or plane.event_metadata[e.metadata_id].name in (
                    ("window",) + HOST_SPANS
                ) or plane.event_metadata[e.metadata_id].name.startswith(
                    "serve."
                )
            ]
            if not events:
                continue
            new = keep.lines.add()
            new.CopyFrom(line)
            del new.events[:]
            for e in events:
                ev = new.events.add()
                ev.CopyFrom(e)
                del ev.stats[:]
                meta = plane.event_metadata[e.metadata_id]
                keep.event_metadata[e.metadata_id].id = meta.id
                keep.event_metadata[e.metadata_id].name = (
                    _op_name(meta.name) if line.name == "XLA Ops" else meta.name
                )
        if device:  # the instructions that ran, by module
            mods = sorted(
                (line.timestamp_ns * 1000 + e.offset_ps,
                 line.timestamp_ns * 1000 + e.offset_ps + e.duration_ps,
                 _SUFFIX.sub("", plane.event_metadata[e.metadata_id].name))
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events
            )
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    t = line.timestamp_ns * 1000 + e.offset_ps
                    for lo, hi, module in mods:
                        if lo <= t < hi:
                            ran.setdefault(module, set()).add(
                                _op_name(plane.event_metadata[e.metadata_id].name)
                            )
        out.planes.add().CopyFrom(keep)
    dst.write_bytes(out.SerializeToString())
    return ran


def main(out: str) -> int:
    import jax

    from repro.configs import get_config
    from repro.core import blocks
    from repro.obs import Tracer, op_scopes
    from repro.serve import ServeEngine

    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: needs a TPU", file=sys.stderr)
        return 2
    annotate = jax.profiler.TraceAnnotation
    cfg = get_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, n_slots=2, max_len=64, page_size=8, decode_impl="xla", seed=0,
        tracer=Tracer(),
    )
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]]
    serve(engine, annotate, prompts)  # compiles every program it runs
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    with annotate("window"):
        serve(engine, annotate, prompts)
    jax.profiler.stop_trace()
    names = blocks.registry.blocks() + list(PROGRAM_SCOPES)
    scopes = {}
    for module, texts in engine.programs.compiled_texts().items():
        for text in texts:
            scopes.setdefault(module, {}).update(
                (k, v) for k, v in op_scopes(text, names).items()
                if v != "other"  # what the map leaves out reads as other
            )
    trace = next(pathlib.Path(out).glob("plugins/profile/*/*.xplane.pb"))
    kept = trim(trace, pathlib.Path(out) / "small_tpu_engine.xplane.pb")
    scopes = {
        module: {k: v for k, v in m.items() if k in kept.get(module, ())}
        for module, m in scopes.items()
    }
    path = pathlib.Path(out) / "small_tpu_engine.scopes.json"
    path.write_text(json.dumps(scopes, sort_keys=True, indent=0) + "\n")
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "modules": sorted(scopes),
        "decode_scopes": sorted(set(scopes.get("jit_decode_fn", {}).values())),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
