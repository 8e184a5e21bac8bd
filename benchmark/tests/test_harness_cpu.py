"""The harness end to end on the CPU, with the look for a chip skipped,
host dispatch and small traffic: a sound run is correct, and each planted
fault and the control make ``correct`` come out false.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import traffic as gen  # noqa: E402

WALKS = {"name": "catchup-test", "loop": "walks",
         "rate_metric": "catchup_rounds_per_s",
         "walk_rounds": {"min": 8, "max": 40, "per_cycle": 4},
         "forged": {"h": 12, "at": 0.5,
                    "kinds": ["v2_swap", "v1_relink", "link_break"]}}
# a seed whose first walk is forged (v2_swap), so that every window,
# however short, holds a forged walk
SEED = next(s for s in range(2**33 + 17, 2**33 + 200)
            if gen.walk_cycle(WALKS, s)[0]["corrupt"])


def _run(*argv: str) -> dict:
    """One process (a fault patches the program); its result line."""
    argv = ["--workload", "catchup-day", "--seconds", "1", *argv]
    code = ("import json, sys; sys.path.insert(0, %r); import run; "
            "print(json.dumps(run.run(%r, chip=False, dispatch='host', "
            "traffic=%r)))" % (BENCH, argv, WALKS))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cycles_keep_every_size_and_forge_in_turn():
    walks = gen.walks(WALKS, SEED)
    sizes = sorted(r["h"] for r in gen.clean_walks(WALKS))
    for i, kind in enumerate(WALKS["forged"]["kinds"] * 2):
        cyc = [next(walks) for _ in range(len(sizes) + 1)]
        assert sorted(r["h"] for r in cyc if not r["corrupt"]) == sizes
        [forged] = [r for r in cyc if r["corrupt"]]
        assert forged == gen.forged_walk(WALKS, kind)


def test_sound_run_is_correct():
    out = _run("--seed", str(SEED))
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", WALKS["rate_metric"]}
    assert list(out)[-1] == "compared"


def test_same_seed_same_walks_and_every_seed_same_work():
    def first(seed, n=3 * (WALKS["walk_rounds"]["per_cycle"] + 1)):
        walks = gen.walks(WALKS, seed)
        return [next(walks) for _ in range(n)]

    big = 2**31 + 12345
    assert first(big) == first(big)
    work = {sum(r["h"] for r in first(s)) for s in (big, big + 1, SEED)}
    assert len(work) == 1


@pytest.mark.parametrize("fault", [
    "v2_unchecked",     # the control
    "verdict_forced",   # an answer altered
    "half_span",        # half the batch left out
])
def test_fault_is_caught(fault):
    out = _run("--seed", str(SEED), "--fault", fault)
    assert not out["correct"], out["compared"]
