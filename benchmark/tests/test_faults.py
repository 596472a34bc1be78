"""``correct`` has to come out false when the timed path is broken
underneath: each fault a FedAvg cell can have is planted in the program
(by the test, through the round function the algorithm hands the host
loop) and the rest of a run is driven as usual."""

import pytest
from conftest import last_line, write_tiny_root


def plant(monkeypatch, fault: str):
    from distributed_learning_simulator_tpu.algorithms.fedavg import FedAvg

    real = FedAvg.make_round_fn

    def make_round_fn(self, *args, **kwargs):
        round_fn = real(self, *args, **kwargs)

        def broken(global_params, client_state, cx, cy, cmask, sizes, key,
                   *rest, **kw):
            n = sizes.shape[0]
            if fault == "state_unchanged":
                _, state, aux = round_fn(
                    global_params, client_state, cx, cy, cmask, sizes, key,
                    *rest, **kw)
                return global_params, state, aux
            # Clients left out of the mean, which is taken over the rest:
            # the second half of them, every second one, or all but one
            # chip's quarter (what a skipped exchange between four chips
            # leaves each chip with).
            if fault == "every_second_left_out":
                sizes = sizes.at[1::2].set(0.0)
            else:
                kept = n // 2 if fault == "half_left_out" else n // 4
                sizes = sizes.at[kept:].set(0.0)
            return round_fn(global_params, client_state, cx, cy, cmask,
                            sizes, key, *rest, **kw)

        return broken

    monkeypatch.setattr(FedAvg, "make_round_fn", make_round_fn)


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_left_out", "every_second_left_out",
              "exchange_left_out"]
)
def test_fault_is_not_correct(bench_run, tmp_path, capsys, monkeypatch,
                              fault):
    plant(monkeypatch, fault)
    root = write_tiny_root(str(tmp_path))
    rc = bench_run.main(
        ["--workload", "tiny_cell", "--seed", "5", "--seconds", "1",
         "--trace", "0"], root=root,
    )
    assert rc == 0
    line = last_line(capsys)
    assert line["correct"] is False
    assert any(v > limit for v, limit in line["compared"].values())
