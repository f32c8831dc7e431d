"""bench.py pieces a CPU can check: the phase heartbeat's flight-recorder
wiring and the serving realism scenario's bookkeeping (on a small model the
test builds itself — bench.py no longer shrinks anything, and refuses to
measure without a TPU: tests/test_chip_smoke.py pins the refusal)."""


def test_heartbeat_beats_blackbox_beacon_and_context():
    # phase attribution: every phase heartbeat beats the bench/phase
    # beacon and stamps the phase into the dump-bundle context
    import bench
    from paddle_tpu.monitor import blackbox
    blackbox.enable(install=False)
    try:
        blackbox.reset()
        bench._heartbeat("unit_test_phase", "start")
        assert blackbox.beacons()["bench/phase"]["count"] >= 1
        assert blackbox.context()["bench_phase"] == "unit_test_phase:start"
        assert any(r["kind"] == "bench_phase"
                   for r in blackbox.ring())
    finally:
        blackbox.disable()
        blackbox.reset()


def test_serve_mixed_reports_latency_percentiles():
    # r5 (VERDICT r4 #7): the serve bench's realism scenario — staggered
    # arrivals, sampling mix, chunked prefill — must produce a positive
    # aggregate rate and ordered latency percentiles
    import bench
    from paddle_tpu.models import GPTConfig

    cfg = GPTConfig(vocab_size=8192, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=256, dropout=0.0)
    tps, p50, p99, t50, t99 = bench.run_serve_mixed(
        2, 4, quiet=True, cfg=cfg, new_tokens=8, chunk=32, dtype=None)
    assert tps > 0
    assert 0 < p50 <= p99      # inter-token
    assert 0 < t50 <= t99      # time-to-first-token
    # chunked prefill + drip arrivals: first tokens cost more than steady
    # decode steps in this scenario
    assert t50 > p50
