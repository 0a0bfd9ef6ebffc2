"""``attention_bwd_device_share.train`` (PR 30): a data file over the
``event_share`` reader.  It resolves for both training cells, reads the
share of the ``attention_bwd`` events from a made-up trace, and reads
nothing where no such kernel ran (the parent's program) or where only the
forward's ``flash_attention`` did."""

import pytest

from harness import resolve, trace

METRIC = "attention_bwd_device_share.train"
CELLS = ["gpt2-medium.train.seq1024", "lfm2-8b-a1b.train.seq4096"]


def made_up(names):
    """A device plane of back-to-back operations of 1000 ns each."""
    hlo = [f"%{n} = bf16[8,64]{{1,0}} custom-call(%p)" for n in names]
    starts = [1000.0 * i for i in range(len(names))]
    return trace.DeviceTrace("/device:TPU:0", hlo, starts,
                             [1000.0] * len(names), [], [], [])


def read(cell_name, names):
    cell = resolve.Cell(cell_name)
    spec = cell.metric_file(METRIC)
    return cell.reader(spec["reader"]).read({"planes": [made_up(names)]},
                                            spec["args"])


@pytest.mark.parametrize("cell_name", CELLS)
def test_metric_resolves_and_reads_a_share(cell_name):
    cell = resolve.Cell(cell_name)
    (entry,) = [m for m in cell.metrics("per_layer") if m["name"] == METRIC]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"],
            entry["layer"]) == ("%", "lower", "device_trace",
                                "train_step_ms", "kernels")
    assert cell.metric_file(METRIC)["layer"] == entry["layer"]
    assert "train_step_ms" in [m["name"] for m in cell.metrics("end_to_end")]
    # two of eight microseconds; a fused kernel's one name counts too
    names = ["fusion.1", "flash_attention.3", "attention_bwd_dkv.5",
             "attention_bwd_dq.6", "copy.2", "fusion.9", "fusion.10",
             "flash_attention.4"]
    assert read(cell_name, names) == pytest.approx(25.0)
    assert read(cell_name, ["attention_bwd.7", "fusion.1"]) \
        == pytest.approx(50.0)


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_program_without_the_kernel_reads_nothing(cell_name):
    assert read(cell_name, ["fusion.350", "flash_attention.14", "copy.1"]) \
        is None
    # an operation that only takes the kernel's result is not the kernel
    assert read(cell_name, ["slice_of_attention_bwd.1", "fusion.2"]) is None


def test_the_serving_cell_does_not_report_it():
    cell = resolve.Cell("gpt2-medium.serve.batch-generate")
    assert METRIC not in [m["name"] for m in cell.metrics("per_layer")]


def test_the_forward_roofline_does_not_count_the_backward():
    """The accepted reader finds forward calls by ``flash_attention`` in
    the kernel's name: the backward's name must not hold it."""
    cell = resolve.Cell(CELLS[0])
    events = cell.metric_file("attention_fwd_roofline.train")[
        "args"]["kernels"][0]["events"]
    plane = made_up(["flash_attention.14", "attention_bwd.7"])
    assert [plane.short_names()[i] for i in plane.matching(events)] \
        == ["flash_attention.14"]
