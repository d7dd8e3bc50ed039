"""Plain references, one module per architecture named in the
configuration files.  They import nothing of the program."""
