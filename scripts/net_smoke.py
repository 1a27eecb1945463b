"""CI smoke: GarblerEndpoint ↔ EvaluatorEndpoint end-to-end over loopback
TCP on a tiny model, plus a multi-client PitGateway pass (two concurrent
sessions, one killed mid-session), with a hard timeout so a deadlocked
socket fails the build fast instead of hanging the runner.

    PYTHONPATH=src python scripts/net_smoke.py [--timeout 180] \\
        [--trace trace.json]

``--trace PATH`` records the whole smoke (both parties + the gateway
pass) with ``repro.obs`` and exports a Chrome trace_event JSON —
validated in CI by ``scripts/trace_check.py`` and uploaded as an
artifact.
"""

import argparse
import signal
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=int, default=180,
                    help="hard wall-clock limit (SIGALRM) in seconds")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="export a Chrome trace_event JSON of the smoke")
    args = ap.parse_args()

    def die(signum, frame):
        print(f"FAIL: net smoke exceeded {args.timeout}s — deadlocked "
              f"socket or runaway exchange", flush=True)
        sys.stdout.flush()
        import os

        os._exit(2)

    signal.signal(signal.SIGALRM, die)
    signal.alarm(args.timeout)

    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro import obs

    if args.trace:
        obs.enable()

    from repro.config import PrivacyConfig
    from repro.core.engine import PrivateTransformer, random_weights
    from repro.net import GarblerEndpoint, PitNetServer, TcpListener, \
        TcpTransport

    D, HEADS, DFF, S = 8, 2, 16, 4
    rng = np.random.default_rng(0)
    weights = random_weights(rng, D, DFF, 1)
    pcfg = PrivacyConfig(he_poly_n=256, he_num_primes=3, he_t_bits=40,
                         frac_bits=6)
    model = PrivateTransformer(pcfg, D, HEADS, DFF, weights, seed=0)

    t0 = time.perf_counter()
    srv = PitNetServer(model, S, impl="ref")
    lst = TcpListener()
    loop = srv.serve_tcp(lst, timeout=120)
    cli = GarblerEndpoint(TcpTransport.connect("127.0.0.1", lst.port),
                          seed=7, impl="ref", timeout=120)
    assert loop.wait_accepted(1, timeout=30), "server never accepted"

    cli.preprocess(1)
    x = rng.normal(0, 1, (S, D))
    y = cli.run(x)

    sess = model.compile_session(S, impl="ref", wire_version=2)
    y_ref = sess.run(x, sess.preprocess(1)[0])
    assert np.array_equal(y, y_ref), "wire output != in-process session"
    assert cli.shared.negotiated_version == 2, "hello did not land on v2"
    led = cli.shared.ledger
    st = sess.stats
    assert led.offline.by_tag == dict(st.channel_offline.by_tag), \
        "offline wire ledger != metered oracle"
    assert led.online.by_tag == dict(st.channel_online.by_tag), \
        "online wire ledger != metered oracle"
    lsum = led.summary()
    assert lsum["rounds_after_coalescing"] < lsum["raw_messages"], \
        "v2 coalescing did not reduce the wire round count"
    err = float(np.abs(y - model.forward_float(x)).max())
    assert err < 0.25, f"accuracy drifted: {err}"

    cli.close()
    lst.close()
    print(f"net smoke OK in {time.perf_counter() - t0:.1f}s: loopback-TCP "
          f"wire v2 output bit-identical, ledger == oracle "
          f"({led.offline.total / 1e6:.1f} MB offline / "
          f"{led.online.total / 1e6:.2f} MB online, "
          f"{lsum['rounds_after_coalescing']} coalesced rounds vs "
          f"{lsum['raw_messages']} metered msgs), max|err|={err:.4f}",
          flush=True)

    # -- gateway: 2 concurrent sessions behind one accept loop, one
    # killed mid-session with a bundle outstanding --------------------
    from repro.serve import PitGateway, gateway_client

    t1 = time.perf_counter()
    gw = PitGateway(model, S, impl="ref", max_sessions=4, pool_cap=4)
    glst = TcpListener()
    gloop = gw.serve_listener(glst, accept_timeout=0.2, timeout=120)
    e1 = gateway_client("127.0.0.1", glst.port, seed=1, impl="ref",
                        timeout=120)
    e2 = gateway_client("127.0.0.1", glst.port, seed=2, impl="ref",
                        timeout=120)
    e1.preprocess(2)  # one to run, one to strand on the kill
    e2.preprocess(1)
    assert np.array_equal(e1.run(x), y_ref), "gateway session 1 diverged"
    e1.offline.transport.close()  # kill: no bye, bundle outstanding
    e1.online.transport.close()
    deadline = time.monotonic() + 30
    while gw.stats()["sessions_active"] != 1:
        assert time.monotonic() < deadline, "victim session never reclaimed"
        time.sleep(0.05)
    assert np.array_equal(e2.run(x), y_ref), "survivor session diverged"
    gst = gw.stats()
    assert gst["bundles_returned"] == 1, gst["bundles_returned"]
    cache = gst["garbling_cache"]
    e2.close()
    gloop.stop()
    gw.close()
    glst.close()
    print(f"gateway smoke OK in {time.perf_counter() - t1:.1f}s: "
          f"2 sessions muxed, mid-session kill returned "
          f"{gst['bundles_returned']} bundle, shared cache "
          f"{cache['slabs']} slabs / {cache['hits']} hits", flush=True)
    if args.trace:
        tr = obs.current()
        tr.export(args.trace)
        rep = tr.report()
        print(f"trace: {len(tr.finished_spans())} spans / "
              f"{len(rep)} span paths -> {args.trace}", flush=True)
    signal.alarm(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
