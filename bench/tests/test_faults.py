"""The harness, with its look for a chip skipped, run at a small size on
the CPU: a sound engine comes out correct, and an engine broken under
the timed path comes out not correct, for each fault a serving cell can
have.  The reference and the control are checked at the same size."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness, spec, traffic
from bench.tests.small import small_cell


def _stale_decode(engine):
    """A decode step that returns the cache it was given."""
    orig = engine._decode

    def step(p, tok, cache, *rest):
        nxt, _, flag, keys = orig(p, tok, cache, *rest)
        return nxt, cache, flag, keys

    engine._decode = step


def _stale_chunk(engine):
    """A prefill chunk that writes nothing into the cache."""
    orig = engine._prefill_chunk

    def chunk(p, toks, cache, *rest):
        first, _, flag, keys = orig(p, toks, cache, *rest)
        return first, cache, flag, keys

    engine._prefill_chunk = chunk


def _altered_token(engine):
    """Every decoded token is replaced where it is produced."""
    orig = engine._decode

    def step(*args):
        nxt, cache, flag, keys = orig(*args)
        return jnp.where(nxt >= 0, (nxt + 7) % 512, nxt), cache, flag, keys

    engine._decode = step


FAULTS = {"sound": None, "stale_decode_state": _stale_decode,
          "stale_prefill_state": _stale_chunk,
          "altered_token": _altered_token}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["stablelm-2-1.6b.chat",
                                      "qwen3-14b-pp4.docs"])
def test_correct_catches_faults(workload, fault):
    cell = small_cell(workload)
    res = harness.run(cell.name, 2**31 + 17, 2.0, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      cell=cell, engine_hook=FAULTS[fault])
    assert res["correct"] is (fault == "sound"), res["checks"]
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == names


def test_fp8_control_fails_the_limit():
    """The control (the reference in fp8 in the program's place) reads
    at least three times the program's widest gap, and above the
    limit."""
    cell = small_cell("stablelm-2-1.6b.chat")
    cfg, settings = cell.config, cell.settings
    drv, w, _, _ = harness.setup(cell, 99, require_tpu=False)
    items = traffic.stream(cell.mix, 99, cfg["vocab_size"],
                           rate=settings["rate_per_s"])
    win = drv.window(items, 2.0, loop="open")
    drv.drain()
    done = [r for r in win.requests if r.done and r.generated]
    chosen = correct.sample(done, settings["check_tokens"], 99)
    ref = spec.reference(cfg["architecture"])
    prog = float(correct.gaps(ref, w, cfg, chosen).max())
    ctrl = correct.gaps(ref, w, cfg, chosen, precision="fp8")
    assert float(ctrl.max()) >= max(3 * prog, settings["max_logit_gap"])
    assert np.count_nonzero(ctrl) > 0
