import os
import sys

# the benchmark imports the program from src/ (works with or without PYTHONPATH=src)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
