"""The per-phase reduction on a hand-made nested trace, checked against a
brute-force count, and the loader on a profile recorded with the JAX
profiler."""

import re

import numpy as np
import pytest

import phases
import trace

CHUNK = "jit(batched)/while/body/closed_call/ssd.chunk/while"
BODY = CHUNK + "/body/closed_call/"
PATHS = [
    "",                                            # 0: no path
    CHUNK,                                         # 1: the chunk's loop
    BODY + "ssd.append/dynamic_update_slice",      # 2
    BODY + "ssd.gc/cond",                          # 3
    BODY + "ssd.gc/ssd.victim/reduce",             # 4
    BODY + "ssd.gc/ssd.drain/compact/pallas_call",  # 5
    BODY + "ssd.valve/while/body/ssd.gc/ssd.drain/add",  # 6
    BODY + "add",                                  # 7: unscoped
    "jit(batched)/while",                          # 8: the outer loop
    "jit(batched)/ssd.sampler/random_bits",        # 9
    BODY + "ssd.movement/ssd.gc/cond",             # 10
    BODY + "ssd.movement/ssd.gc/ssd.victim/reduce",  # 11
]
S = 10**6  # ns per unit


def hand_made(cut: bool):
    """A job: the sampler, then an outer loop over three chunks. Each
    chunk holds an append, a GC conditional around a victim search and a
    drain, a valve op, an unscoped op, a movement conditional around a
    victim search, and loop time no op covers. The third chunk runs past
    the job's end; where ``cut``, the profiler dropped its loop event and
    the outer loop's, and the runner returns long after the last op."""
    ev = [(2, 4, 9), (5, 200, 8)]
    for c0 in (5, 70, 130):
        ev += [
            (c0, c0 + 60, 1),
            (c0 + 1, c0 + 5, 2),
            (c0 + 6, c0 + 30, 3),
            (c0 + 7, c0 + 12, 4),
            (c0 + 13, c0 + 28, 5),
            (c0 + 31, c0 + 38, 6),
            (c0 + 40, c0 + 43, 7),
            (c0 + 45, c0 + 55, 10),
            (c0 + 46, c0 + 50, 11),
        ]
    if cut:
        ev = [x for x in ev if x[2] != 8 and not (x[0] == 130 and x[2] == 1)]
    ev.sort()
    start = np.array([a for a, _, _ in ev]) * S
    end = np.array([b for _, b, _ in ev]) * S
    pid = np.array([p for _, _, p in ev])
    job_end = (400 if cut else 180) * S
    host = [(1 * S, job_end, "bench.job"),
            (1 * S, job_end, "bench.runner"),
            (1 * S, 6 * S, "fleet.build"),
            (60 * S, 72 * S, "fleet.stack")]
    return {
        "devices": {"/device:TPU:0": (start, end, pid)},
        "host": host,
        "names": [f"%op.{i} = s32[] add(%a, %b)" if i != 1
                  else "%while.1 = (s32[]) while(%t)"
                  for i in range(len(PATHS))],
        "op_paths": {"/device:TPU:0": pid},
        "paths": PATHS,
        "path_stats": ["tf_op"],
    }


def brute_force(tr):
    """Walk every nanosecond of the window: the innermost op event there
    (the latest start, then the earliest end) and the complete chunk
    around it, if any, name its owner."""
    lo = min(s for s, _, n in tr["host"] if n == "bench.job")
    hi = lo + round(trace.reduce(tr)["window_s"] * 1e9)
    start, end, pid = tr["devices"]["/device:TPU:0"]
    chunks = [(a, b) for a, b, p in zip(start, end, pid)
              if p == 1 and a >= lo and b <= hi]
    out = {}
    for t in range(lo, hi, S // 4):
        cover = [i for i in range(len(start)) if start[i] <= t < end[i]]
        if not cover or not any(a <= t < b for a, b in chunks):
            continue
        i = max(cover, key=lambda i: (start[i], -end[i]))
        path = PATHS[pid[i]]
        if pid[i] == 1:
            owner = "loop"
        else:
            sc = [p for p in path.split("ssd.chunk/")[-1].split("/")
                  if p.startswith("ssd.")]
            owner = sc[0] if sc else "unscoped"
        out[owner] = out.get(owner, 0) + (S // 4) * 1e-9
    return out, chunks


@pytest.mark.parametrize("cut", [False, True])
def test_scopes_against_brute_force(cut):
    tr = hand_made(cut)
    s = phases.reduce(tr)
    sc = s["scopes"]
    want, chunks = brute_force(tr)
    assert sc["chunks"] == len(chunks) == 2
    assert sc["chunk_s"] == pytest.approx(60e-3 * len(chunks))
    assert sc["phases"] == pytest.approx(
        {k: v for k, v in want.items() if k.startswith("ssd.")})
    assert sc["loop_s"] == pytest.approx(want["loop"])
    assert sc["unscoped_s"] == pytest.approx(want["unscoped"])
    # every instant of a chunk goes to exactly one owner
    assert (sum(sc["phases"].values()) + sc["loop_s"] + sc["unscoped_s"]
            == pytest.approx(sc["chunk_s"], abs=1e-12))
    assert sc["crossing"] == 0
    assert sc["ops"] == 8 * len(chunks)
    assert sc["outside"] == pytest.approx({"ssd.sampler": 2e-3})


def test_outermost_phase_owns_an_op():
    """A valve's GC counts as valve, a movement's as movement, and the
    victim search and drain refine GC only in the paths."""
    sc = phases.reduce(hand_made(False))["scopes"]
    assert set(sc["phases"]) == {"ssd.append", "ssd.gc", "ssd.valve",
                                 "ssd.movement"}
    assert sc["phases"]["ssd.valve"] == pytest.approx(2 * 7e-3)
    # movement's cond (10 units) holds a victim search (4 units)
    assert sc["phases"]["ssd.movement"] == pytest.approx(2 * 10e-3)
    assert sc["paths"]["ssd.movement/ssd.gc/ssd.victim"] == pytest.approx(
        2 * 4e-3)
    assert sc["paths"]["ssd.valve/ssd.gc/ssd.drain"] == pytest.approx(
        2 * 7e-3)
    assert sc["paths"]["ssd.gc"] == pytest.approx(2 * 4e-3)
    assert (sc["paths"]["ssd.gc"] + sc["paths"]["ssd.gc/ssd.victim"]
            + sc["paths"]["ssd.gc/ssd.drain"]) == pytest.approx(
        sc["phases"]["ssd.gc"])


@pytest.mark.parametrize("cut", [False, True])
def test_old_readings_unchanged_and_gaps_named_by_fleet_spans(cut):
    tr = hand_made(cut)
    new = phases.reduce(tr)
    old = trace.reduce(dict(tr, host=[h for h in tr["host"]
                                      if h[2].startswith("bench.")]))
    for key in ("busy_s", "window_s", "ops", "cut_s", "devices"):
        assert new[key] == old[key]
    assert [g for _, g in new["gaps"]] == [g for _, g in old["gaps"]]
    assert cut == (old["cut_s"] > 0)
    # the gaps around the sampler lie in drive construction; where the
    # outer loop is missing, the one between two chunks lies in stacking
    names = {n for n, _ in new["gaps"]}
    assert "fleet.build" in names
    assert cut == ("fleet.stack" in names)
    assert not any(n.startswith("fleet.") for n, _ in old["gaps"])


def test_self_times_against_brute_force():
    rng = np.random.default_rng(0)
    ivs = []

    def split(a, b, depth):
        ivs.append((a, b))
        t = a
        while depth < 4 and t < b - 2:
            u = int(rng.integers(t + 1, b))
            v = int(rng.integers(u + 1, b + 1))
            split(u, v, depth + 1)
            t = v
    split(0, 400, 0)
    rng.shuffle(ivs)
    start = np.array([a for a, _ in ivs])
    end = np.array([b for _, b in ivs])
    self_ns, crossing = phases.self_times(start, end)
    assert crossing == 0
    want = np.zeros(len(ivs), np.int64)
    for t in range(400):
        cover = [i for i in range(len(ivs)) if start[i] <= t < end[i]]
        want[max(cover, key=lambda i: (start[i], -end[i]))] += 1
    assert self_ns.tolist() == want.tolist()


def test_program_paths_find_the_chunk_loop_of_a_compiled_scan():
    """The compiled HLO of a chunked scan names its chunk loop by one
    while instruction, and the ops of its body by their phases."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def chunk(c, _):
            def step(c2, _):
                with jax.named_scope("ssd.append"):
                    c2 = c2.at[3].add(1.0)
                with jax.named_scope("ssd.gc"):
                    v = jnp.argmin(c2)
                    c2 = jax.lax.cond(v > 2, jnp.sin, jnp.cos, c2)
                return c2, None

            with jax.named_scope("ssd.chunk"):
                c, _ = jax.lax.scan(step, c, None, length=5)
            return c, c[0]

        return jax.lax.scan(chunk, x, None, length=3)

    text = jax.jit(f).lower(jnp.arange(8.0)).compile().as_text()
    module, ops = phases.program_paths(text)
    assert module == "jit_f"
    loops = [k for k, v in ops.items() if phases.scope_of(v)[1]
             and re.search(rf"%{re.escape(k)} = [^\n]*"
                           + re.escape(phases.WHILE), text)]
    assert len(loops) == 1
    found = {phases.scope_of(v)[0][:1] for v in ops.values()}
    assert {("ssd.append",), ("ssd.gc",)} <= found


def test_load_names_ops_by_their_program(tmp_path):
    """Ops get the path their program's HLO gives their instruction; the
    same instruction name in another program, or outside any, gets none;
    fleet.* host spans are kept; op times come exact from the events'
    ps stats, where start_ns, a float of ns since the epoch, is 256 ns
    apart."""
    from jax.profiler import ProfileData

    def ev(meta, start, length, stats=""):
        return (f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
                f"duration_ps: {length * 1000} {stats}}}")

    def op(meta, start, length):
        return ev(meta, start, length,
                  f"stats {{ metadata_id: 100 uint64_value: {start * 1000} }}"
                  f" stats {{ metadata_id: 101 uint64_value: {length * 1000} "
                  "} ")

    def meta(key, name):
        return (f'event_metadata {{ key: {key} value {{ id: {key} '
                f'name: "{name}" }} }}')

    xspace = "\n".join([
        'planes { id: 1 name: "/device:TPU:0"',
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0',
        ev(10, 0, 100), ev(11, 200, 50), "}",
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0',
        op(1, 1, 90), op(2, 2, 3), op(2, 210, 3), op(2, 300, 3), "}",
        meta(1, "%while.3 = (s32[]) while(%t)"),
        meta(2, "%fusion.1 = s32[] fusion(%p)"),
        meta(10, "jit_batched(123)"), meta(11, "jit_stack(456)"),
        'stat_metadata { key: 100 value { id: 100 name: "device_offset_ps" '
        "} }",
        'stat_metadata { key: 101 value { id: 101 name: "device_duration_ps"'
        " } }", "}",
        'planes { id: 2 name: "/host:CPU"',
        'lines { id: 1 name: "python" timestamp_ns: 0',
        ev(1, 0, 400), ev(2, 0, 50), ev(3, 0, 60), "}",
        meta(1, "bench.job"), meta(2, "fleet.build"), meta(3, "other"),
        "}",
    ])
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(xspace))
    hlo = "\n".join([
        "HloModule jit_batched, is_scheduled=true",
        f'  %while.3 = (s32[]) while(%t), metadata={{op_name="{CHUNK}"}}',
        f'  ROOT %fusion.1 = s32[] fusion(%p), kind=kLoop, '
        f'metadata={{op_name="{BODY}ssd.gc/add" stack_frame_id=3}}',
    ])
    tr = phases.load(tmp_path, {0}, [hlo])
    start, end, name = tr["devices"]["/device:TPU:0"]
    assert start.tolist() == [1, 2, 210, 300]
    s_ps, e_ps = tr["op_ps"]["/device:TPU:0"]
    assert s_ps.tolist() == [1000, 2000, 210000, 300000]
    assert e_ps.tolist() == [91000, 5000, 213000, 303000]
    assert [tr["paths"][p] for p in tr["op_paths"]["/device:TPU:0"]] == [
        CHUNK, BODY + "ssd.gc/add", "", ""]
    assert sorted(n for _, _, n in tr["host"]) == ["bench.job",
                                                   "fleet.build"]
    old = trace.load(tmp_path, {0})
    assert old["names"] == tr["names"]
    for a, b in zip(old["devices"]["/device:TPU:0"], (start, end, name)):
        assert a.tolist() == b.tolist()
    assert set(old["host"]) < set(tr["host"])


def test_scope_of_tells_the_chunk_loop_from_its_body():
    assert phases.scope_of(CHUNK) == ((), True)
    assert phases.scope_of(CHUNK + ":while") == ((), True)
    assert phases.scope_of(BODY + "add") == ((), False)
    assert phases.scope_of(BODY + "ssd.valve/while") == (("ssd.valve",),
                                                        False)
    assert phases.scope_of(
        BODY + "ssd.movement/ssd.gc/ssd.victim/reduce") == (
        ("ssd.movement", "ssd.gc", "ssd.victim"), False)
    assert phases.scope_of("jit(batched)/ssd.sampler/x") == (
        ("ssd.sampler",), False)
    assert phases.scope_of("") == ((), False)


def test_metrics_per_host_write():
    s = phases.reduce(hand_made(False))
    m = phases.metrics(s, chunk_writes=1000)
    writes = 2 * 1000
    assert m["step.append_us"] == pytest.approx(1e6 * 8e-3 / writes)
    assert m["step.gc_us"] == pytest.approx(
        1e6 * (s["scopes"]["phases"]["ssd.gc"] + 14e-3) / writes)
    assert m["step.movement_us"] == pytest.approx(1e6 * 20e-3 / writes)
    assert "step.detector_us" not in m and "step.interval_us" not in m
    assert m["scan.overhead_share"] == pytest.approx(
        s["scopes"]["loop_s"] / 120e-3)
    assert m["fleet.build_share"] == pytest.approx(5 / 179)
    assert phases.table(s, 1000)[1].startswith("ssd.gc")
    # a trace of a program without scopes reads no phase metric
    bare = hand_made(False)
    bare["paths"] = [""] * len(PATHS)
    m = phases.metrics(phases.reduce(bare), 1000)
    assert not any(k.startswith(("step.", "scan.")) for k in m)


def test_ops_without_a_phase_fault_the_run():
    tr = hand_made(False)
    assert phases.fault(phases.reduce(tr)) is None
    tr["paths"] = [p if i < 2 else BODY + "add"
                   for i, p in enumerate(PATHS)]
    assert "ssd.*" in phases.fault(phases.reduce(tr))


def test_load_keeps_fleet_spans_of_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.job"):
        for name in ("fleet.build", "fleet.stack", "fleet.dispatch",
                     "fleet.resolve"):
            with jax.profiler.TraceAnnotation(name):
                x = f(x)
        x.block_until_ready()
    jax.profiler.stop_trace()
    tr = phases.load(tmp_path, {0})
    names = [n for _, _, n in sorted(tr["host"])]
    assert names == ["bench.job", "fleet.build", "fleet.stack",
                     "fleet.dispatch", "fleet.resolve"]
    s = phases.reduce(tr)
    assert s["window_s"] == trace.reduce(trace.load(tmp_path, {0}))[
        "window_s"]
    assert s["scopes"]["chunks"] == 0
    assert 0 < phases.metrics(s, 1)["fleet.build_share"] < 1
