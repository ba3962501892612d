"""Token-lease fast path: microsecond admission for hot resources.

A resource guarded only by simple QPS rules admits host-side
(`core/lease.py`) with device-exact window math; statistics stream to
the device asynchronously. Run and compare the per-entry latency with
what a device dispatch would cost.
"""

import _demo_env  # noqa: F401  (pins JAX platform; import first)

import time

import sentinel_tpu as st


def main():
    eng = st.get_engine()
    st.load_flow_rules([st.FlowRule(resource="checkout", count=100)])
    assert "checkout" in eng._leases, "simple QPS rules are lease-eligible"

    h = st.entry_ok("checkout")  # warm (starts the background committer)
    if h:
        h.exit()

    lat = []
    for _ in range(500):
        t0 = time.perf_counter()
        h = st.entry_ok("checkout")
        lat.append((time.perf_counter() - t0) * 1e6)
        if h:
            h.exit()
    lat.sort()
    print(f"leased entry latency over {len(lat)} calls: "
          f"p50={lat[len(lat) // 2]:.1f}µs  p99={lat[int(len(lat) * .99)]:.1f}µs")

    # quota still enforced exactly — burst past 100/s blocks. Sleep a FULL
    # window from here so every latency-loop bucket expires (aligning to
    # the wall second alone would retain the previous 500ms bucket).
    time.sleep(1.1)
    handles = [st.entry_ok("checkout") for _ in range(120)]
    admitted = sum(1 for h in handles if h)
    print(f"burst of 120 against count=100: admitted {admitted}")
    for h in handles:
        if h:
            h.exit()

    # the device converges within a committer flush: ops-plane view
    deadline = time.time() + 30
    while time.time() < deadline:
        snap = eng.node_snapshot().get("checkout", {})
        if snap.get("passQps", 0) > 0:
            print("device stats:", {k: snap[k]
                                    for k in ("passQps", "blockQps")})
            break
        time.sleep(0.2)


if __name__ == "__main__":
    main()
