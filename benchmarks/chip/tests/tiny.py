"""The tiny manifest the CPU tests drive the harness with."""
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")

MANIFEST = {
    "configs": [{"name": "tiny-llama", "file": "configs/tiny-llama.json"}],
    "workloads": [
        {"name": "tiny-train", "config": "tiny-llama",
         "traffic": "tiny-train", "chips": 1},
        {"name": "tiny-backlog", "config": "tiny-llama",
         "traffic": "tiny-backlog", "chips": 1},
        {"name": "tiny-steady", "config": "tiny-llama",
         "traffic": "tiny-steady", "chips": 1},
    ],
    "end_to_end": [
        {"name": "train_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-train"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


def files():
    from chiplib import manifest

    return manifest.Files(root=DATA, data=DATA, manifest=MANIFEST)


def interpret_flash():
    """The CPU has no Mosaic: the program's flash kernel in interpret
    mode, steered here in the test and not by an option of the program."""
    from paddle_tpu.ops.pallas import flash_attention

    flash_attention.register(platform="cpu", interpret=True)
