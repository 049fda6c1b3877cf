"""What the benchmark's tests share: where the repo is, how to copy the
benchmark's data files, and the sizes of the tiny cells the CPU can hold."""
import os
import shutil


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA_DIRS = ("configs", "traffic", "limits", "metrics")

TINY = {
    "configs/mistral7b-train.json": dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=256, num_hidden_layers=2),
    "traffic/train-4k.json": dict(seq_len=128, ce_chunk=128),
    "configs/resnet50.json": dict(stage_sizes=[1, 1, 1, 1], num_filters=16,
                                  num_classes=10, image_size=64),
    "traffic/train-b256.json": dict(batch=64),
    # set from six seeds on the CPU at these sizes (perf/calibrate.py
    # --root <tiny copy>), as PERF.md sets the cells' own on the chip: above
    # the program's largest reading, below the least of the control's, the
    # half batch's and the unchanged state's (which reads 1). null: shown,
    # not compared. gpt: grad 0.0018 | 0.40, grad_median 0.00046 | 0.13,
    # change 0.0043 | 0.025, change_median 0.00047 | 0.006. resnet:
    # grad_median 0.0093 | 0.26, change_median 0.021 | 0.32, state 0.016 | 1.
    "limits/mistral7b-train-4k.json": dict(
        loss1=None, loss2=None, loss3=None, grad=0.02, grad_median=0.01,
        change=0.012, change_median=0.002),
    "limits/resnet50-train-b256.json": dict(
        loss1=None, loss2=None, loss3=None, grad=None, grad_median=0.06,
        change=None, change_median=0.1, state=0.3),
}


def copy_data(root: str, to: str) -> None:
    shutil.copy(os.path.join(root, "BENCHMARK.json"), to)
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(root, "perf", sub),
                        os.path.join(to, "perf", sub))
