"""docs/backends.md — drive the WAMI DSE on the measured backend.

Replay mode: deterministic, no TPU, prices come from the recording
checked in under artifacts/measurements/.
"""

from repro.apps.wami.pallas import wami_pallas_oracle, wami_pallas_session


def main():
    session = wami_pallas_session(delta=0.25, workers=8)   # replay mode
    result = session.run()                                 # no TPU needed
    print(f"{result.total_invocations} invocations, "
          f"theta in [{result.theta_min:.1f}, {result.theta_max:.1f}] fps")
    for point in result.pareto():
        print(f"  theta {point.perf:8.2f}  cost {point.cost:12.1f}")

    # explicit-oracle form, e.g. to re-record on new hardware (compiled
    # on a TPU; pass interpret=True to time the interpreter on a CPU):
    oracle = wami_pallas_oracle("record", interpret=True)
    session = wami_pallas_session(delta=0.25, oracle=oracle)
    session.run()
    print("recording written to", oracle.flush())


if __name__ == "__main__":
    main()
