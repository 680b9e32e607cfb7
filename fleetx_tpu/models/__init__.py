"""Module registry (reference ``ppfleetx/models/__init__.py:28-32``).

The reference resolves ``cfg.Model.module`` with ``eval()``; here an explicit
registry maps module names to task classes.
"""

from __future__ import annotations

__all__ = ["build_module", "get_registry"]


def get_registry():
    """Name → task-module class map (lazy imports keep startup light)."""
    from fleetx_tpu.core.module import GPTModule

    modules = {"GPTModule": GPTModule}
    try:
        from fleetx_tpu.core.module import GPTGenerationModule, GPTEvalModule
        modules["GPTGenerationModule"] = GPTGenerationModule
        modules["GPTEvalModule"] = GPTEvalModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.finetune.module import LoRAGPTModule
        modules["LoRAGPTModule"] = LoRAGPTModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.vision.module import GeneralClsModule
        modules["GeneralClsModule"] = GeneralClsModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.ernie.module import ErnieModule
        modules["ErnieModule"] = ErnieModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.imagen.module import ImagenModule
        modules["ImagenModule"] = ImagenModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.mla_moe.module import MLAMoEModule
        modules["MLAMoEModule"] = MLAMoEModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.swa_moe.module import SWAMoEModule
        modules["SWAMoEModule"] = SWAMoEModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.gdn_mla.module import GDNMLAModule
        modules["GDNMLAModule"] = GDNMLAModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.conv_moe.module import ConvMoEModule
        modules["ConvMoEModule"] = ConvMoEModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.samba_y.module import SambaYModule
        modules["SambaYModule"] = SambaYModule
    except ImportError:
        pass
    try:
        from fleetx_tpu.models.ssm_mqa.module import SSMMQAModule
        modules["SSMMQAModule"] = SSMMQAModule
    except ImportError:
        pass
    return modules


def build_module(cfg):
    """Instantiate the task module named by ``cfg.Model.module``."""
    modules = get_registry()
    model_cfg = cfg.get("Model", {}) if hasattr(cfg, "get") else {}
    name = model_cfg.get("module", "GPTModule")
    cls = modules.get(name)
    if cls is None:
        raise ValueError(f"unknown module {name!r}; have {sorted(modules)}")
    return cls(cfg)
