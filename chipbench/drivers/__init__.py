"""The ways a cell is driven. A traffic file names one by its module's name."""
