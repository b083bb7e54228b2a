"""Writes fixtures/small.xplane.pb from the text below: a trace in the
format a v5e run records (plane, line and instruction names copied from the
PR 22 trace of deepfm-criteo.resident), cut to one scan of one step so that
every expected number can be worked out by hand. All times in microseconds
from the line's start:

  XLA Ops        while.42 [0,100] { fusion.302 [0,30]  place_sorted_grads.4 [30,50]
                                    (idle [50,60])  all-reduce.7 [60,70]  multiply_add_fusion.32 [70,100] }
  Async XLA Ops  all-gather-start.1 [20,45]   all-gather-start.2 [45,55]
  host           bench.dispatch [0,5]   bench.readback [5,100]

busy 90 of 100; the Mosaic call 20; collectives cover [20,55] and [60,70] = 45,
of which [50,55] and [60,70] = 15 run beside no other operation.
"""

import os

from jax.profiler import ProfileData

NAMES = {
    1: "%while.42 = (s32[]{:T(128)}, f32[1]{0:T(128)}) while(%tuple.1), condition=%cond, body=%body",
    2: "%fusion.302 = f32[212992,11]{0,1:T(8,128)S(1)} fusion(f32[33800192,11]{0,1:T(8,128)} %get-tuple-element.2197, s32[212992]{0:T(1024)S(1)} %copy), kind=kCustom",
    3: "%place_sorted_grads.4 = f32[11,33800192]{1,0:T(8,128)} custom-call(s32[16504]{0:T(1024)S(1)} %get-tuple-element.796, s32[1,213504]{1,0:T(1,128)S(1)} %broadcast_in_dim.296, f32[16,213504]{1,0:T(8,128)S(1)} %pad.45), custom_call_target=\\\"tpu_custom_call\\\"",
    4: "%all-reduce.7 = f32[400,400]{1,0:T(8,128)} all-reduce(f32[400,400]{1,0:T(8,128)} %dot.3), replica_groups={{0,1,2,3}}, to_apply=%add",
    5: "%multiply_add_fusion.32 = (f32[33800192,11]{0,1:T(8,128)}, f32[33800192,11]{0,1:T(8,128)}) fusion(f32[33800192,11]{0,1:T(8,128)} %get-tuple-element.2197), kind=kLoop",
    6: "%all-gather-start.1 = (s32[8192,26]{1,0}, s32[32768,26]{1,0}) all-gather-start(s32[8192,26]{1,0} %ids), dimensions={0}",
    7: "%all-gather-start.2 = (f32[8192,286]{1,0}, f32[32768,286]{1,0}) all-gather-start(f32[8192,286]{1,0} %grads), dimensions={0}",
    8: "bench.dispatch",
    9: "bench.readback",
    10: "jit__lambda(5008496477862552487)",
}


def event(meta, start_us, end_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * 1_000_000} "
            f"duration_ps: {(end_us - start_us) * 1_000_000} }}")


def metadata(ids):
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{NAMES[i]}" }} }}'
        for i in ids)


TEXT = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 5000000 {event(10, 0, 100)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 5000000
    {event(1, 0, 100)} {event(2, 0, 30)} {event(3, 30, 50)} {event(4, 60, 70)}
    {event(5, 70, 100)} }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 5000000
    {event(6, 20, 45)} {event(7, 45, 55)} }}
  {metadata([1, 2, 3, 4, 5, 6, 7, 10])}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 5000000
    {event(8, 0, 5)} {event(9, 5, 100)} }}
  {metadata([8, 9])}
}}
"""

if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "small.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT))
