"""The readers of the program's own tracing on a small recorded trace
(``bench/testdata/small_trace_spans.pbtxt``; times in ns).

On the device: a decode, a prefill, a train step, an insert program and
a second compiled decode program (its own module: the same instruction
names, other name stacks).  As on a TPU, an op event names only its HLO
instruction; the profile's
``/host:metadata`` plane holds each program's optimized module, whose
instructions carry their name stacks (``OP_NAMES`` below: the
``adapter`` and ``quanta`` scopes, also under ``vmap``, ``jvp`` and
``transpose`` and fused with ``;``, beside plain base ops, an
``adapter_subtree`` op that is no ``adapter`` scope, and a ``while`` op
with no name stack holding the decode's ops).  On the host: the
program's spans inside the benchmark's ticks: ``serve.tick`` >
``serve.land`` > ``serve.wait``, a chunk and an adapter load that wait
too, a tick that did no work and a tick cut by the window's end; on
another thread the runtime's ``AllocateBufferAwait``, inside a dispatch,
overlapping a ``serve.wait`` and between ticks.
"""

import pytest

from bench import harness as H, program_trace as P, trace as T
from conftest import TESTDATA

BODY = "jit({})/while/body/closed_call/"
OP_NAMES = {
    "jit_decode_fn(17)": {
        "fusion.2": BODY.format("decode_fn") + "dot_general",
        "fusion.3": BODY.format("decode_fn") + "adapter/vmap(quanta)/dot_general",
        "gather.4": BODY.format("decode_fn") + "adapter/jit(_take)/gather",
        "fusion.5": BODY.format("decode_fn") + "adapter_subtree/add",
        "banked_lora_delta.6":
            BODY.format("decode_fn") + "adapter/banked_lora_delta/pallas_call",
    },
    "jit_prefill_fn(18)": {
        "fusion.7": BODY.format("prefill_fn") + "adapter/vmap(quanta)/dot_general",
    },
    "jit_train_step(19)": {
        # the same instruction name as a decode op: looked up per program
        "fusion.2": BODY.format("train_step") + "quanta/reshape",
        "fusion.8": BODY.format("train_step")
        + "jvp()/while/body/closed_call/quanta/dot_general",
        "fusion.9": BODY.format("train_step") + "transpose(jvp())/while/body/"
        "closed_call/checkpoint/quanta/transpose;quanta/transpose",
        "fusion.10": BODY.format("train_step") + "transpose(jvp())/while/body/"
        "closed_call/checkpoint/dot_general",
    },
    "jit_insert_fn(20)": {"fusion.11": "jit(insert_fn)/scatter"},
    # a second compile of the decode: fusion.3 is a base op here
    "jit_decode_fn(27)": {"fusion.3": BODY.format("decode_fn") + "dot_general"},
}


def _hlo_proto(program, op_names) -> bytes:
    """A serialized ``HloProto`` whose module has ``op_names``' instructions
    (each a negate: only names and metadata are read)."""
    from jax._src.lib import xla_client

    lines = [f"HloModule {program.split('(')[0]}", "",
             "ENTRY %main (p: f32[4]) -> f32[4] {",
             "  %p = f32[4]{0} parameter(0)"]
    prev = "p"
    for i, (ins, op) in enumerate(op_names.items()):
        root = "ROOT " if i == len(op_names) - 1 else ""
        lines.append(f'  {root}%{ins} = f32[4]{{0}} negate(%{prev}), '
                     f'metadata={{op_name="{op}"}}')
        prev = ins
    lines.append("}")
    module = xla_client._xla.hlo_module_from_text("\n".join(lines))
    raw = module.as_serialized_hlo_module_proto()
    n, size = len(raw), b""
    while True:                      # HloProto field 1: the module
        size += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            break
    return b"\x0a" + size + raw


def _metadata_plane() -> str:
    entries = []
    for i, (program, op_names) in enumerate(OP_NAMES.items(), 1):
        raw = "".join(f"\\{b:03o}" for b in _hlo_proto(program, op_names))
        entries.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                       f'"{program}" stats {{ metadata_id: 1 bytes_value: '
                       f'"{raw}" }} }} }}')
    return ('planes { id: 3 name: "/host:metadata" ' + " ".join(entries)
            + ' stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } }')


def _profile_text(name):
    with open(TESTDATA / name) as f:
        text = f.read()
    return text + _metadata_plane() if name == "small_trace_spans.pbtxt" \
        else text


def _profile(name):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_profile_text(name))


@pytest.fixture(scope="module")
def summary():
    return T.from_profile(_profile("small_trace_spans.pbtxt"))


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """The recorded trace where ``bench/run.py`` writes a profile."""
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        _profile_text("small_trace_spans.pbtxt"))
    out = tmp_path / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path)
    return tmp_path


def _read(metric, summary):
    ctx = H.Context(config={}, traffic={}, peaks={}, counters={},
                    trace=summary)
    return H.load_reader(metric)(ctx)


@pytest.mark.parametrize("label, scope, hit", [
    ("jit(decode_fn)/while/body/adapter/quanta/dot_general", "adapter", True),
    ("jit(decode_fn)/while/body/adapter/quanta/dot_general", "quanta", True),
    ("jit(prefill_fn)/adapter/vmap(quanta)/dot_general", "quanta", True),
    ("jit(train_step)/transpose(jvp(quanta))/dot_general", "quanta", True),
    ("jit(train_step)/checkpoint/rematted_computation/reshape;quanta/add",
     "quanta", True),
    ("jit(decode_fn)/while/body/adapter_subtree/add", "adapter", False),
    ("jit(decode_fn)/while/body/dot_general", "quanta", False),
    ("jit(quanta_fn)/add", "quanta", False),
])
def test_scope_label(label, scope, hit):
    assert bool(P.scope_label(scope).search(label)) is hit


def test_hlo_op_names_per_program(trace_dir):
    # keyed by the compiled module: two compiles of one function apart
    assert P.hlo_op_names() == OP_NAMES
    assert P.hlo_op_names(trace_dir / "none") == {}


def test_scope_time_counts_self_time_inside_one_program(summary, trace_dir):
    names = P.hlo_op_names()
    # decode: the quanta chain (1000), the bank's gather (500) and the
    # LoRA delta kernel (500); not the while op holding them (no name
    # stack), adapter_subtree, fusion.2 (quanta only in train_step), nor
    # fusion.3 in the second decode program (a base op there)
    assert P.scope_time(summary, "decode_fn", "adapter", names) == \
        pytest.approx((2000e-9, 3))
    assert P.scope_time(summary, "decode_fn", "quanta", names) == \
        pytest.approx((1000e-9, 1))
    # train: the forward (1000) and the backward under checkpoint (1500)
    assert P.scope_time(summary, "train_step", "quanta", names) == \
        pytest.approx((2500e-9, 2))
    assert P.scope_time(summary, "prefill_fn", "quanta", names) == \
        pytest.approx((500e-9, 1))
    assert P.scope_time(summary, "train_step", "adapter", names) == (0.0, 0)
    assert P.scope_time(summary, "decode_fn", "adapter", {}) == (0.0, 0)


def test_scopes_leave_kernel_names_alone(summary):
    assert summary.kernel_time("banked_lora_delta", module="decode_fn") == \
        pytest.approx((500e-9, 1))


def test_program_spans_inside_the_window(summary, trace_dir):
    spans = P.program_spans(summary.window)
    names = sorted(s.name for s in spans)
    # the fourth tick ends after the window: it is left out, the
    # dispatch inside it that ends before the window's end is not
    assert names.count("serve.tick") == 3
    assert names.count("serve.wait") == 3
    assert names.count("serve.dispatch") == 2
    assert names.count("AllocateBufferAwait") == 3
    assert {"serve.land", "serve.grow", "serve.stream", "serve.chunk",
            "serve.admit", "pool.load"} <= set(names)
    assert not [n for n in names if n.startswith("bench.")]
    assert P.program_spans(summary.window, trace_dir / "none") == []


def test_host_work_leaves_out_waits_and_idle_ticks(summary, trace_dir):
    # tick 0: 7000 - 100 (the runtime's wait in the dispatch) - 1700
    # (serve.wait in serve.land and the runtime's wait that overlaps it,
    # counted once); tick 2: 5600 - 500 (chunk) - 900 (adapter load);
    # tick 1 did no work; the runtime's wait between ticks counts nowhere
    spans = P.program_spans(summary.window)
    assert P.host_work_ms(spans) == pytest.approx((5200 + 4200) / 2 * 1e-6)
    no_runtime = [s for s in spans if s.name not in P.RUNTIME_WAITS]
    assert P.host_work_ms(no_runtime) == \
        pytest.approx((5400 + 4200) / 2 * 1e-6)
    assert P.host_work_ms([s for s in spans if s.name != "serve.tick"]) \
        is None


def test_readers_on_hand_worked_numbers(summary, trace_dir):
    # the adapter work of both decode programs' runs (6000 + 400)
    assert _read("adapter_share.itl", summary) == \
        pytest.approx(100 * 2000 / 6400)
    assert _read("quanta_share.train", summary) == \
        pytest.approx(100 * 2500 / 4000)
    assert _read("host_work_ms_per_tick.itl", summary) == \
        pytest.approx(0.0047)


def test_readers_find_nothing_without_the_programs_tracing(tmp_path,
                                                           monkeypatch):
    # the older trace has no name scopes, no train step and no program
    # spans: what a program without this tracing leaves
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path)
    old = T.from_profile(_profile("small_trace.pbtxt"))
    for metric in ("adapter_share.itl", "quanta_share.train",
                   "host_work_ms_per_tick.itl"):
        assert _read(metric, old) is None, metric
        assert _read(metric, None) is None, metric
