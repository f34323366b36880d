"""Configuration files: the published config.json keys as run, plus the
cut, the deployment and the departures.  What depends on the architecture
lives in ``bench/arch/<model_type>.py`` (see bench/arch/__init__.py):
``arch`` reads the shapes the benchmark's yardstick needs (work counts,
the plain reference); ``model_config`` builds the program's
``ModelConfig`` from the same keys.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ARCH_DIR = Path(__file__).resolve().parents[1] / "arch"


def load(path) -> dict:
    return json.loads(Path(path).read_text())


def architecture(c: dict, where: Path = ARCH_DIR):
    """The module ``<where>/<model_type>.py`` of this configuration."""
    model_type = str(c["model_type"])
    path = Path(where) / f"{model_type}.py"
    if not re.fullmatch(r"\w+", model_type) or not path.is_file():
        raise ValueError(f"unsupported model_type {model_type!r}: no "
                         f"architecture module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_arch_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(c: dict) -> dict:
    """Shapes of the configuration's architecture from config.json keys."""
    return architecture(c).arch(c)


def model_config(c: dict, name: str):
    """The program's ModelConfig for this configuration file."""
    return architecture(c).model_config(c, name)
