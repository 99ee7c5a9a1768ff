"""The benchmark's own library: cell lookup, chips, seeds, traces,
comparisons and the result line. Nothing here imports the program
except where a driver hands it the program's objects."""
