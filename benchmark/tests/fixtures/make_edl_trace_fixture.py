"""Writes fixtures/edl.xplane.pb from the text below: a trace shaped like the
job cell's (no `bench.*` annotation, so the window is device 0's module runs
less the first and the last) with the worker loop's `edl.*` spans on one host
line and the parse pool's on another. All times in microseconds:

  XLA Modules  m0 [0,100]  m1 [150,250]  m2 [310,440]  m3 [450,550]    window [150,450]
  XLA Ops      fusion [0,100] [160,250] [310,440] [450,550]            gaps [150,160] [250,310] [440,450]
  task loop    edl.task_turn [100,290] { edl.task [105,270] { edl.compute [152,255] {
                   edl.h2d [153,158]  edl.compute.dispatch [158,161]  edl.compute.readback [161,254] } }
                 edl.report [272,288] { edl.ckpt.save [275,280] } }
               edl.task_turn [295,460] { edl.lease [296,300]  edl.task [300,455] {
                   edl.data_wait [301,304] { edl.input.make_batch [302,303] }
                   edl.compute [305,452] { edl.h2d [306,309]  edl.compute.dispatch [309,311]
                                           edl.compute.readback [311,451] } } }
  parse pool   edl.input.make_batch [240,300]       (covers most of the long gap, attributes nothing)

By hand, innermost span first, a span without a bucket of its own taking the
one around it:
  gap [150,160]: task 2, compute 1, h2d 5, dispatch 2
  gap [250,310]: readback 4, compute 1, task 15, turn 2, report 16 (5 of them under
                 ckpt.save), turn 2, NO SPAN 5, turn 1, lease 4, task 1, data_wait 3
                 (1 of them under the loop thread's make_batch, which never attributes),
                 task 1, compute 1, h2d 3, dispatch 1
  gap [440,450]: readback 10
idle 80, named 75; input 3, h2d 8, step 20, turn 25, loop 19; two dispatches
(the `edl.compute` spans that begin inside the window).
"""

import os

from jax.profiler import ProfileData

FUSION = ("%fusion.302 = f32[212992,11]{0,1:T(8,128)S(1)} fusion(f32[33800192,11]{0,1:T(8,128)} "
          "%get-tuple-element.2197), kind=kCustom")
MODULE = "jit_train_many(5008496477862552487)"
LOOP = [
    ("edl.task_turn", 100, 290), ("edl.task", 105, 270), ("edl.compute", 152, 255),
    ("edl.h2d", 153, 158), ("edl.compute.dispatch", 158, 161),
    ("edl.compute.readback", 161, 254), ("edl.report", 272, 288),
    ("edl.ckpt.save", 275, 280),
    ("edl.task_turn", 295, 460), ("edl.lease", 296, 300), ("edl.task", 300, 455),
    ("edl.data_wait", 301, 304), ("edl.input.make_batch", 302, 303),
    ("edl.compute", 305, 452), ("edl.h2d", 306, 309),
    ("edl.compute.dispatch", 309, 311), ("edl.compute.readback", 311, 451),
]
POOL = [("edl.input.make_batch", 240, 300)]
HOST_NAMES = sorted({name for name, _, _ in LOOP + POOL})
HOST_IDS = {name: i + 1 for i, name in enumerate(HOST_NAMES)}


def event(meta, start_us, end_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * 1_000_000} "
            f"duration_ps: {(end_us - start_us) * 1_000_000} }}")


def metadata(names_by_id):
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'
        for i, name in names_by_id.items())


def host_line(line_id, spans):
    return (f'lines {{ id: {line_id} name: "python" timestamp_ns: 5000000 '
            + " ".join(event(HOST_IDS[n], s, e) for n, s, e in spans) + " }")


TEXT = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 5000000
    {event(2, 0, 100)} {event(2, 150, 250)} {event(2, 310, 440)} {event(2, 450, 550)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 5000000
    {event(1, 0, 100)} {event(1, 160, 250)} {event(1, 310, 440)} {event(1, 450, 550)} }}
  {metadata({1: FUSION, 2: MODULE})}
}}
planes {{
  id: 2 name: "/host:CPU"
  {host_line(1, LOOP)}
  {host_line(2, POOL)}
  {metadata({i: n for n, i in HOST_IDS.items()})}
}}
"""

if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "edl.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT))
