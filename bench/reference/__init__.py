"""Plain references, one module per architecture, named by the
``reference`` key of a configuration file.  They import nothing of the
program under test."""
