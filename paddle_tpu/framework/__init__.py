from .desc import (BlockDesc, BlockRef, BlocksRef, OpDesc, ProgramDesc,
                   VarDesc, VarType)
from .framework import (Block, Operator, Parameter, Program, Variable,
                        convert_dtype, default_main_program,
                        default_startup_program, grad_var_name, name_scope,
                        program_guard,
                        switch_main_program, switch_startup_program)
from . import unique_name
