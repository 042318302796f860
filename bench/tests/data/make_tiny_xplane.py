"""Writes tiny.xplane.pb: a trace in the layout of a TPU v5e profile,
for bench/tests/test_bench_trace.py. One device plane: "XLA Modules"
holds three calls of one program; "XLA Ops" holds, per call, a while
loop around a fusion and a kernel custom call, then a copy. One host
plane: bench.step and bench.idle spans inside bench.window.

    python bench/tests/data/make_tiny_xplane.py
"""
import pathlib

from jax.profiler import ProfileData

PS_PER_US = 1_000_000
DEVICE = {"jit_step(42)": 1, "%while.3 = (s32[]) while(...)": 2,
          "%fusion.7 = f32[256,256] fusion(...)": 3,
          "%sla_fwd.2 = (f32[8,256,128]) custom-call(...)": 4,
          "%copy.1 = f32[256] copy(...)": 5}
HOST = {"bench.window": 1, "bench.step": 2, "bench.idle": 3}


def _line(i, name, events):
    body = "".join(f"    events {{ metadata_id: {m} offset_ps: "
                   f"{o * PS_PER_US} duration_ps: {d * PS_PER_US} }}\n"
                   for m, o, d in events)
    return (f"  lines {{\n    id: {i}\n    name: \"{name}\"\n"
            f"    timestamp_ns: 0\n{body}  }}\n")


def _meta(names):
    return "".join(f"  event_metadata {{ key: {v} value {{ id: {v} "
                   f"name: \"{k}\" }} }}\n" for k, v in names.items())


def xspace() -> bytes:
    modules, ops, host = [], [], [(1, 0, 3200)]   # (metadata, start, dur) us
    for i in range(3):
        s = 100 + i * 1000
        host += [(2, s, 600), (3, s + 600, 400)]
        modules.append((1, s + 50, 500))
        ops += [(2, s + 60, 400), (3, s + 70, 150), (4, s + 230, 200),
                (5, s + 480, 60)]
    text = ("planes {\n  id: 1\n  name: \"/device:TPU:0\"\n"
            + _line(1, "XLA Modules", modules) + _line(2, "XLA Ops", ops)
            + _meta(DEVICE) + "}\n"
            + "planes {\n  id: 2\n  name: \"/host:CPU\"\n"
            + _line(1, "python", host) + _meta(HOST) + "}\n")
    return ProfileData.text_proto_to_serialized_xspace(text)


if __name__ == "__main__":
    out = pathlib.Path(__file__).resolve().parent / "tiny.xplane.pb"
    out.write_bytes(xspace())
