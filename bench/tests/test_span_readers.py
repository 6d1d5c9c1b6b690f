"""The readers of the program's federation-step spans, on hand-made records."""
import pytest

from bench import common

READERS = common.per_layer_readers()
PHASE_OF = {"encode_ms": "encode", "decode_ms": "decode", "d2h_ms": "host_params",
            "h2d_ms": "set_params"}


@pytest.mark.parametrize("metric", sorted(PHASE_OF))
def test_phase_total_per_round(metric):
    record = {"kind": "train", "rounds": 4,
              "phases": {PHASE_OF[metric]: [8, 2.0], "push": [4, 9.0]}}
    assert READERS[metric](record) == {"value": 500.0, "unit": "ms"}


@pytest.mark.parametrize("metric", sorted(PHASE_OF))
def test_silent_where_the_program_has_no_such_span(metric):
    # the parent commit's program records none of these phases in the cell
    assert READERS[metric]({"kind": "train", "rounds": 3,
                            "phases": {"push": [3, 1.0], "pull": [3, 2.0]}}) is None
    assert READERS[metric]({"kind": "train", "rounds": 0,
                            "phases": {PHASE_OF[metric]: [1, 1.0]}}) is None
