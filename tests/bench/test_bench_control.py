"""The control of the comparison that decides ``correct``, at a size the
CPU holds: the reference put in the program's place in float8 (e4m3)
must read a wider served-token gap than the limit on every seed, and
``check.judge`` must call it not correct, while the program's own served
tokens stay within the limit."""
from bench import calibrate

import bench_tiny


def test_fp8_control_fails_the_limit(tmp_path):
    bench = bench_tiny.make_root(tmp_path)
    limit = 0.003
    rows = calibrate.calibrate("tiny.mix", [1, 2, 3], True, root=tmp_path,
                               bench=bench, require_chip=False,
                               log=lambda *_: None)
    for r in rows:
        assert r["tokens"] == 32
        assert r["gap"] <= limit, r
        assert r["control_gap"] > limit, r
        assert r["control_correct"] is False, r
