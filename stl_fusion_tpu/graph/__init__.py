"""Host↔device graph backend: DeviceGraph container + live hub mirror."""
from .backend import RowBlock, TpuGraphBackend
from .device_graph import DeviceGraph
from .device_info import require_accelerator
from .nonblocking import WavePipeline, WaveTicket
from .program_cache import enable_program_cache, program_cache_stats
from .superround import SuperRoundProgram, SuperRoundTicket

__all__ = [
    "TpuGraphBackend",
    "RowBlock",
    "DeviceGraph",
    "WavePipeline",
    "WaveTicket",
    "SuperRoundProgram",
    "SuperRoundTicket",
    "enable_program_cache",
    "program_cache_stats",
    "require_accelerator",
]
