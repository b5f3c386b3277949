"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, per-layer
metric or request path is found by its name: ``configs/<config>.json``
(named by the entry's ``file``), ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``drivers/<driver>.py`` (named by the mix).
``checks/<workload>.json`` holds the limits of the cell's output check.
Adding a cell means adding such files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# keys of the configuration file -> the program's ModelConfig fields
PROGRAM_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "sliding_window": "sliding_window", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token", "hidden_act": "mlp_act",
}


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    root: Path
    bench: dict

    @classmethod
    def load(cls, root: Path) -> "Spec":
        return cls(root, json.loads((root / "BENCHMARK.json").read_text()))

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == cell["config"])
        return json.loads((self.root / entry["file"]).read_text())

    def mix(self, cell: dict) -> dict:
        return json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                          .read_text())

    def limits(self, cell: dict) -> dict:
        return json.loads((HERE / "checks" / f"{cell['name']}.json")
                          .read_text())

    def metrics(self, cell: dict, section: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[section]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def program_config(self, cell: dict):
        return program_config(self.config(cell))


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: its arch with
    the file's overrides, checked key by key against the file."""
    from repro.configs import get_config

    prog = cfg["program"]
    pcfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    for key, field in PROGRAM_FIELDS.items():
        have = getattr(pcfg, field)
        if have != cfg[key]:
            raise ValueError(f"{cfg['name']}: the program runs {field}={have!r}"
                             f" where the configuration states {key}="
                             f"{cfg[key]!r}")
    # what the reference implements: a causal stack of one repeated layer,
    # windowed GQA attention with RoPE, a SwiGLU MLP or top-k experts
    plain = (pcfg.group_size == 1 and pcfg.causal and pcfg.use_rope
             and not pcfg.embed_inputs and pcfg.mrope_sections is None
             and pcfg.attn_logit_softcap is None
             and pcfg.final_logit_softcap is None and not pcfg.qk_norm
             and pcfg.attn_scale is None and not pcfg.scale_embeddings
             and not pcfg.shared_expert
             and pcfg.pattern[0].mixer == "attn"
             and pcfg.pattern[0].attn_kind == "local"
             and pcfg.pattern[0].moe == bool(cfg["num_local_experts"]))
    if not plain:
        raise ValueError(f"{cfg['name']}: the program runs a layer the "
                         "reference does not describe")
    return pcfg
