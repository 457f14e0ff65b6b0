"""With the timed path broken underneath, a run reads ``correct``
false: for each fault a cell can have. Small copies of the cells, on
the CPU; the harness's look for a chip is skipped."""
import json
import os
import subprocess
import sys

from conftest import ROOT, TINY_QWEN


def _alter_token(system):
    """A token altered where the engine produces it."""
    decode, vocab = system.engine.scheduler_decode, system.vocab

    def altered(req):
        return (decode(req) + 1) % vocab
    system.engine.scheduler_decode = altered


def _stale_state(system):
    """A decode step that returns its state unchanged."""
    decode = system.engine.scheduler_decode

    def stale(req):
        before = req.runtime
        tok = decode(req)
        req.runtime = (before[0],) + tuple(req.runtime[1:])
        return tok
    system.engine.scheduler_decode = stale


def test_sound_run_is_correct(tiny_run):
    _, out = tiny_run("qwen15-4b.solo-long")
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["max_logit_gap"]["value"] \
        <= out["compared"]["max_logit_gap"]["limit"]


def test_altered_token_is_caught(tiny_run):
    _, out = tiny_run("qwen15-4b.solo-long", fault=_alter_token)
    assert out["correct"] is False
    assert out["compared"]["max_logit_gap"]["value"] \
        > out["compared"]["max_logit_gap"]["limit"]


def test_unchanged_state_is_caught(tiny_run):
    _, out = tiny_run("qwen15-4b.solo-long", fault=_stale_state)
    assert out["correct"] is False


def test_chunk_altered_on_the_wire_is_caught(tiny_run):
    """The fabric delivers a token chunk with a bit flipped: the client's
    tokens differ from what the engine produced."""
    def flip_chunks(system):
        transport = system.fabric.transport
        deliver = transport.deliver

        def flipped(messages):
            delivery = deliver(messages)
            for m in delivery.messages:
                if m.frame.is_reply and m.frame.is_stream and m.frame.bufs:
                    m.frame.bufs[0][0] ^= 1
            return delivery
        transport.deliver = flipped
    _, out = tiny_run("qwen15-4b.solo-long", fault=flip_chunks)
    assert out["correct"] is False
    assert out["compared"]["fabric_token_mismatches"]["value"] > 0


def test_exchange_faults_are_caught():
    """Four virtual CPU devices in a child process (the flag has to be
    set before JAX starts): a sound exchange reads 0 mismatched bytes,
    one left out or one with a byte altered reads more."""
    code = f"""
import json, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
from bench import calibrate, core
from bench.trace import TracedWindow
params = core.load_json(core.BENCH / "configs" / "tfgrpc-ici-4ep.json")
traffic = core.traffic_params("fc-skew")
builder = core.config_builder("tfgrpc-ici-4ep")
faults = dict(none=None, **calibrate._exchange_faults())
for name, fault in faults.items():
    system = builder.build(params, traffic, 2**31 + 9)
    if fault is not None:
        fault(system)
    system.warmup(traffic)
    system.run(traffic, 2**31 + 9, 0.5, TracedWindow(False, 0.0))
    print(json.dumps(dict(fault=name, compared=system.check(0))))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = {r["fault"]: r["compared"]["mismatched_bytes"]["value"]
           for r in map(json.loads, out.stdout.splitlines())}
    assert got["none"] == 0
    assert got["exchange_left_out"] > 0 and got["answer_altered"] > 0


def test_control_reads_far_above_the_program():
    """The float8 control against the bf16 program, at a small size:
    the control's gap is several times the program's."""
    from bench import calibrate
    rs = list(calibrate.readings("qwen15-4b.solo-long", 2, 2, 1.0,
                                 overrides=TINY_QWEN))
    program = max(r["compared"]["max_logit_gap"]["value"] for r in rs)
    control = min(r["control_gap"] for r in rs)
    assert control >= 3 * program, (program, control)


#: at this file's small size the program reads at most 0.063 and the
#: float8 control at least 0.189 (14 seeds each on the CPU); the cell's
#: own limit, 0.35, is set from readings at its own size on the chip
SMALL_LIMIT = 0.12


def test_control_in_the_programs_place_is_not_correct(tiny_run):
    """The float8 control put in the program's place, through the run's
    own check and judgement: ``correct`` reads false."""
    from bench import calibrate
    small = dict(TINY_QWEN, correctness={"max_logit_gap": SMALL_LIMIT})
    _, sound = tiny_run("qwen15-4b.solo-long", overrides=small)
    assert sound["correct"] is True
    _, out = tiny_run("qwen15-4b.solo-long", overrides=small,
                      fault=calibrate.float8_control)
    assert out["correct"] is False
    assert out["compared"]["max_logit_gap"]["value"] > SMALL_LIMIT
