"""``prefill_ms_per_ktok`` in a backlog, where each prefill takes a
server loop's time from decoding and so moves ``tokens_per_s``."""
import os

from benchlib.spec import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "prefill_ms_per_ktok.py")).read
