"""Share of the traced window in which no operation ran on the chip, in %:
``device_idle.mixed``'s reader, read for the screening cell."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("device_idle.mixed.py")))["read"]
