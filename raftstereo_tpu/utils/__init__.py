"""Utilities: checkpoint conversion, fault injection, logging, misc."""

from .convert import convert_checkpoint, load_state_dict, torch_to_variables
from .faults import FaultPlan, InjectedCrash, InjectedFault, InjectedSampleError
from .platform import describe_runtime, setup_compile_cache

__all__ = ["describe_runtime", "setup_compile_cache", "convert_checkpoint", "load_state_dict",
           "torch_to_variables", "FaultPlan", "InjectedFault",
           "InjectedCrash", "InjectedSampleError"]
