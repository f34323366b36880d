"""The benchmark's copy of the request generator: the same requests as the
serve loop's, the same sizes on every seed, and a warm-up that runs every
prompt length and chunk length of the window."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import traffic  # noqa: E402

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "doc-decode.json").read_text())


@pytest.mark.parametrize("extra", [
    {},
    {"--shared-prefix": 0, "--trace": "uniform"},
    {"--priority-classes": 3, "--arrival-rate": 0.0},
])
def test_copy_makes_the_serve_loops_requests(extra):
    from repro.launch import serve
    flags = dict(MIX["flags"], **{"--prompt": 96, "--shared-prefix": 40,
                                  "--gen": 8, "--seed": 7, "--requests": 9})
    flags.update(extra)
    ours = traffic.make_requests(flags, 9, 7, 300)
    theirs = serve._make_requests(serve.parse_args(traffic.argv(flags)), 300)
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs, strict=True):
        np.testing.assert_array_equal(a.prompt, np.asarray(b["prompt"]))
        assert (a.id, a.gen, a.priority, a.arrival) == \
            (b["id"], b["gen"], b["priority"], b["arrival"])


def test_every_seed_serves_the_same_sizes():
    """The same prompt lengths and the same answer lengths on every seed,
    in another order."""
    flags = MIX["flags"]
    sizes, orders = [], []
    for seed in (1, 2, 3_000_000_123):
        s = traffic.program_seed(flags, 40, seed, 16160)
        reqs = traffic.make_requests(flags, 40, s, 16160)
        sizes.append((sorted(len(r.prompt) for r in reqs),
                      sorted(r.gen for r in reqs)))
        orders.append([(len(r.prompt), r.gen) for r in reqs])
    assert sizes[0] == sizes[1] == sizes[2]
    assert len({tuple(o) for o in orders}) == 3


def test_warmup_runs_every_shape_of_the_window():
    flags = dict(MIX["flags"], **{"--seed": 5, "--requests": 40})
    reqs = traffic.make_requests(flags, 40, 5, 16160)
    warm = traffic.warmup_flags(flags, reqs, MIX["warmup_requests"], 16160)
    wreqs = traffic.make_requests(warm, warm["--requests"], warm["--seed"],
                                  16160)
    assert traffic.chunk_shapes(flags, reqs, True) <= \
        traffic.chunk_shapes(warm, wreqs, False)
    assert {len(r.prompt) for r in reqs} <= {len(r.prompt) for r in wreqs}
    assert {k: v for k, v in warm.items() if k not in ("--seed", "--requests")} \
        == {k: v for k, v in flags.items() if k not in ("--seed", "--requests")}
