"""`correct` has to come out false when the timed path is broken
underneath, and when a lower precision stands in the program's place.

The fault test skips the harness's look for a chip (the CPU rehearsal
at toy widths), plants the one fault a serving cell can have, a token
altered where it is produced, and drives the rest of a run through
run.main with the limits the cells' files hold. The unbroken rehearsal
is driven too and has to come out correct under the same limits.

The control test puts the reference with int8 operands in the engine's
place, at a size a test run can hold, and sees `correct.verdict` fail
it under each cell's limits. The readings at the cells' own sizes are
in PERF.md.

    python3 -m pytest perfbench/tests/test_broken_path.py -q
"""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402
from perfbench.lib import common, correct  # noqa: E402

CELLS = ["gpt2-large.chat", "gpt2-large.score"]


def drive(workload, capfd, seed=11):
    """One rehearsal run; returns (correct, {number: value})."""
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", "0", "--rehearse-on-cpu"])
    assert rc == 0
    err = capfd.readouterr().err
    numbers = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"perfbench (?:compared|read) (\S+)=(\S+) ", err)}
    verdict = re.findall(r"perfbench correct=(true|false)", err)
    assert verdict, err[-2000:]
    return verdict[-1] == "true", numbers


@pytest.mark.parametrize("workload", CELLS)
def test_altered_token_is_not_correct(workload, monkeypatch, capfd):
    from paddle_tpu.serving import programs
    limit = common.load_json("cells", workload + ".json")["limits"][
        "served_gap_per_near_tie"]
    ok, sound = drive(workload, capfd)
    assert ok and sound["served_gap_per_near_tie"] < limit
    orig = programs._pick

    def altered(logits, *a, **k):
        return (orig(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(programs, "_pick", altered)
    broken_ok, broken = drive(workload, capfd)
    assert not broken_ok
    assert broken["served_gap_per_near_tie"] > limit


@pytest.mark.parametrize("workload", CELLS)
def test_int8_in_the_engines_place_is_not_correct(workload):
    """The control of the serving cells, as far as a CPU can hold it:
    the reference with int8 operands picks tokens whose float32 logit
    lies further below the best, per near tie, than the cell's limits
    allow, and `verdict` says so. The published width and heads at a
    depth, a context and a vocabulary that a test can hold; the same
    prompts and tokens in bfloat16 pass, and in float32 read nought."""
    from perfbench.lib import traffic as _traffic
    _, _, config, _, limits, reference, _ = common.cell_files(workload)
    cfg = {**config, "n_layer": 8, "n_ctx": 128, "n_positions": 128,
           "vocab_size": 20000, "assumed": {"padded_vocab_size": 20096}}
    params = reference.make_params(cfg, _traffic.jax_key(9), "bfloat16")
    rng = np.random.default_rng(9)
    sample = [{"ids": rng.integers(0, 20000, 40, dtype=np.int32),
               "out": rng.integers(0, 20000, 80, dtype=np.int32)}
              for _ in range(12)]

    def numbers(precision):
        return correct.control_serve(reference, params, cfg, sample,
                                     precision, limits["limits"])

    assert not correct.verdict(numbers("int8"))
    assert correct.verdict(numbers("bfloat16"))
    exact = {n: v for n, v, _ in numbers("float32")}
    assert exact["served_gap_per_near_tie"] == 0
